"""
What the set-up ledger (PR 37) costs, on the host it runs on: the bracket
of a first call with nothing inside it, a `jax.monitoring` listener's
call, a build scope, and the entry points' thread-local assignment, each
in microseconds over N repetitions. No device work: the numbers are the
host's (run it through chiprun for the chip's host, as span_cost.py did
for the spans).

    chiprun -- python3 chipbench/tests/ledger_cost.py [N]
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def per_call_us(fn, n):
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return 1e6 * (time.perf_counter() - t0) / n


def main(n=20000):
    n = int(n)
    from jax import monitoring
    from dedalus_tpu.tools import metrics, retrace
    sentinel = retrace.sentinel
    state = retrace.TraceCount("ledger_cost")
    phases = metrics.BuildPhases("LedgerCost")

    def bracket():
        row = sentinel.open_row(state, phases)
        row.discovered()
        row.close()

    def listener():
        monitoring.record_event_time_span(
            "/jax/core/compile/jaxpr_trace_duration", 1.0, 2.0,
            fun_name="ledger_cost")

    def scope():
        with phases.scope("upload"):
            pass

    lookup = {"compile": 0.0}
    out = {
        "n": n,
        "first_call_bracket_us": per_call_us(bracket, min(n, 2000)),
        "listener_call_us": per_call_us(listener, n),
        "build_scope_us": per_call_us(scope, n),
        "enter_assignment_us": per_call_us(phases.enter, n),
        "old_dict_lookup_us": per_call_us(lambda: "compile" in lookup, n),
        "empty_call_us": per_call_us(lambda: None, n),
    }
    sentinel.reset()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
