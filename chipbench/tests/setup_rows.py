"""
One run of a cell through run.py's own `main`, then the whole set-up
ledger of the process beside its log: every program row (run.py's checks
line carries the deployment's solver's twelve largest only), the eager
aggregate by name, and every solver's build-phase record with the
process-level one. Nothing of the run differs from `python3 -m
chipbench.run`: the ledger is read after `main` returns.

    chiprun -- python3 chipbench/tests/setup_rows.py <ledger.json> [--stacks-every <seconds>] --workload <cell> --seed <n> --seconds 10 --trace <0|1>

`--stacks-every N`: a daemon thread of this script writes the main
thread's Python stack into <ledger.json>.stacks every N seconds (plain
`sys._current_frames()`: where a long first call spends its time, seen
from beside it; `faulthandler.dump_traceback_later` segfaulted the TPU
process in three runs of three, PR 37).
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import run                                # noqa: E402


def watch_stacks(path, every):
    """Start the daemon thread that appends the main thread's stack to
    `path` every `every` seconds."""
    import threading
    import time
    import traceback
    main_id = threading.main_thread().ident
    started = time.time()

    def loop():
        with open(path, "w") as out:
            while True:
                time.sleep(every)
                frame = sys._current_frames().get(main_id)
                if frame is None:
                    return
                out.write(f"--- {time.time() - started:.1f} s\n"
                          + "".join(traceback.format_stack(frame)[-14:]))
                out.flush()

    threading.Thread(target=loop, daemon=True).start()


def main(path, *args):
    args = list(args)
    if args[:1] == ["--stacks-every"]:
        watch_stacks(path + ".stacks", float(args[1]))
        args = args[2:]
    rc = run.main(args)
    try:
        from dedalus_tpu.tools import metrics, retrace
        sentinel = retrace.sentinel
        ledger = {"rows": sentinel.program_rows(),
                  "totals": sentinel.program_totals(),
                  "eager": sentinel.eager_programs(),
                  "solvers": [dict(p.record(), name=p.name)
                              for p in metrics.all_phases()],
                  "process": metrics.process_phases().record()}
    except (ImportError, AttributeError) as exc:    # a tree without one
        ledger = {"no_ledger": repr(exc)}
    for record in ledger.get("solvers", []) + [ledger.get("process", {})]:
        record.pop("programs", None)
    pathlib.Path(path).write_text(json.dumps(ledger))
    return rc


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
