#!/usr/bin/env python3
"""Where the host's time goes inside a cell's calls, by the program's own
``lp.*`` spans, laid over the device's ops on the profiler's clock.

    python3 bench/span_report.py --workload dense28_b100k --seed 7

Set-up as in ``run.py``; then one pass over the pool with a ``SpanTracer``
active and the profiler off, and the calls of a traced window as a
``--trace 1`` run traces them (``measure.window``: at least
``measure.TRACE_S`` seconds and one pass over the pool).  Prints one JSON
object: ``untraced_span_ms``, the mean milliseconds per call of each span
with the profiler off; ``spans.breakdown`` of the traced calls;
``host_lead_ms`` and ``host_tail_ms`` as the benchmark reads them; and the
traced calls per second.  It checks no answers.  Off a TPU it exits
non-zero and prints nothing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import devtrace, gen, measure, program, spans
    from bench.spec import BENCH, load_cell

    cell = load_cell(args.workload)
    try:
        jax, devices = measure.init_jax(cell.chips)
    except measure.NoChip as e:
        print(f"span_report: {e}", file=sys.stderr)
        return 3
    core = program.load_core()
    pool = gen.make_pool(cell.config, cell.traffic, cell.batch, args.seed)
    inputs = [program.to_input(core, d) for d in pool]
    call = program.entry(core, cell.config)
    call(inputs[-1])
    setup_s = time.perf_counter() - T_START

    untraced = spans.mean_ms(spans.replay(cell.config, pool))
    log_dir = BENCH / ".cache" / "span_trace"
    shutil.rmtree(log_dir, ignore_errors=True)
    _, traced = measure.window(call, inputs, measure.TRACE_S, jax,
                               trace_dir=str(log_dir))
    trace = devtrace.read(str(log_dir), len(devices))
    events = spans.read(str(log_dir))
    shutil.rmtree(log_dir, ignore_errors=True)
    lead, tail = trace.lead_tail_ms()
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "setup_s": setup_s,
        "device": devices[0].device_kind, "untraced_span_ms": untraced,
        "traced_calls": traced,
        "traced_calls_per_s": traced / trace.window_s(),
        "host_lead_ms": lead, "host_tail_ms": tail,
        **spans.breakdown(trace, events)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
