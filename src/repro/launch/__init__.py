# Launchers: the TNN clustering service (serve_tnn.py; run as __main__).
