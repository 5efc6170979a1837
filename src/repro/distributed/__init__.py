# Distributed runtime: async checkpointing (checkpoint) and straggler
# monitoring (straggler), used by the service, the DSE and serve/durability.
from repro.distributed import checkpoint, straggler  # noqa: F401
