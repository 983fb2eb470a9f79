#!/usr/bin/env python
"""The one process that holds the chip: ``dlt-serve`` with a profiler switch.

    python benchmark/serve_child.py <ack-dir> -- <dlt-serve arguments>

runs ``distributed_llms_tpu.cli.serve_main.main`` on the main thread, so the
path is the server's own.  Only the process that holds the chip can trace
it, so a side thread reads commands from standard input, one a line:
``trace_start <dir>`` and ``trace_stop``.  Each is acknowledged by an empty
file ``<ack-dir>/<command>`` once ``jax.profiler`` has returned.
"""

from __future__ import annotations

import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _control(ack_dir: str) -> None:
    import jax

    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "trace_start":
            jax.profiler.start_trace(words[1])
        elif words[0] == "trace_stop":
            jax.profiler.stop_trace()
        else:
            continue
        with open(os.path.join(ack_dir, words[0]), "w"):
            pass


def main(argv: list[str]) -> None:
    ack_dir, dashes, *serve_argv = argv
    if dashes != "--":
        raise SystemExit(__doc__)
    sys.path.insert(0, ROOT)
    from distributed_llms_tpu.cli import serve_main

    threading.Thread(target=_control, args=(ack_dir,), daemon=True,
                     name="trace-control").start()
    serve_main.main(serve_argv)


if __name__ == "__main__":
    main(sys.argv[1:])
