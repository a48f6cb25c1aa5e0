"""Traffic drivers. A traffic file names its driver (``"driver":
"serve"``); the harness imports ``bench.drivers.<driver>`` and calls its
``run``."""
