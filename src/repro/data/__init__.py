# Data substrate: UCR archive access (real format or synthetic doubles) for
# the TNN clustering pillar.
from repro.data import ucr  # noqa: F401
