"""The program's server, started through its own entry point.

``python -m benchmark.server_entry <memory.json> <server flags...>`` runs
``fantoch_tpu.bin.server.main`` unchanged and, once it has returned (the
SIGTERM path leaves a final snapshot and returns normally), writes what
only the process that owns the chip can read: each device's peak bytes in
use.  The snapshot the program writes carries no memory reading.
"""

from __future__ import annotations

import json
import sys


def main(argv: list[str]) -> None:
    memory_path, server_argv = argv[0], argv[1:]
    from fantoch_tpu.bin.server import main as server_main

    server_main(server_argv)  # raises (SystemExit) where the server failed
    import jax

    peaks = [
        int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for device in jax.local_devices()
    ]
    with open(memory_path, "w") as fh:
        json.dump({"peak_bytes_in_use": peaks}, fh)


if __name__ == "__main__":
    main(sys.argv[1:])
