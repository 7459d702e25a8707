"""A role's entry point in a traced run: the program's own ``cli.main``, with
the program's round spans switched on and, in worker ``w0``, jax's profiler
around one whole round.

The spans: ``HYPHA_TRACE_DIR`` is the program's switch, but every CLI role
reads ``HYPHA_*`` as configuration and refuses the unknown key ``trace_dir``,
so the switch is thrown through ``telemetry.trace.enable`` here instead
(``PERFBENCH_SPAN_DIR``, ``PERFBENCH_NODE``).

The profiler (``PERFBENCH_PROFILE_DIR``, ``w0`` only): only the process that
holds the chip can trace it, and the program has no profiler hook yet, so
the benchmark brings its own: a ``logging`` handler that sees the worker's
``round N done`` record. The trace opens at round 0's
close (where the measured span opens) and closes at round 1's, so it holds
one whole warm round: H inner steps and one outer sync. Closing and writing
it is left to a thread, so the training thread goes straight on.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from pathlib import Path

FIRST_ROUND = 0  # the trace opens when this round closes


class RoundTrigger(logging.Handler):
    def __init__(self, out_dir: Path) -> None:
        super().__init__(logging.INFO)
        self.out_dir = out_dir
        self.marks: dict = {}
        self.stopper: threading.Thread | None = None

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.msg
        if not isinstance(msg, str) or not msg.startswith("round %d done"):
            return
        done = record.args[0]
        try:
            if done == FIRST_ROUND and not self.marks:
                self._open()
            elif done == FIRST_ROUND + 1 and self.marks:
                self._close_in_a_thread()
        except Exception:  # the job goes on untraced; the harness says so
            logging.getLogger("perfbench").exception("profiler hook failed")

    def _open(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no per-call python events: they swamp the file
        opts.host_tracer_level = 1
        self.out_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self.marks["start_wall_ns"] = time.time_ns()

    def _close_in_a_thread(self) -> None:
        if self.stopper is not None:
            return
        self.marks["stop_wall_ns"] = time.time_ns()
        self.stopper = threading.Thread(target=self._close, name="perfbench-trace")
        self.stopper.start()

    def _close(self) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
            self.marks["written_wall_ns"] = time.time_ns()
        except Exception:
            logging.getLogger("perfbench").exception("stop_trace failed")
            self.marks["error"] = "stop_trace failed"
        tmp = self.out_dir / "marks.json.tmp"
        tmp.write_text(json.dumps(self.marks))
        os.replace(tmp, self.out_dir / "marks.json")


def main() -> int:
    span_dir = os.environ.get("PERFBENCH_SPAN_DIR")
    if span_dir:
        from hypha_tpu.telemetry import trace

        trace.enable(span_dir, os.environ.get("PERFBENCH_NODE", "node"))
    out_dir = os.environ.get("PERFBENCH_PROFILE_DIR")
    if out_dir:
        logging.getLogger("hypha.executor.training").addHandler(RoundTrigger(Path(out_dir)))
    from hypha_tpu.cli import main as cli_main

    return cli_main(sys.argv[1:])


if __name__ == "__main__":
    raise SystemExit(main())
