"""Record a short traced window of a cell and keep its ``.xplane.pb``.

    python chipbench/record_trace.py --workload <cell> --seconds 0.3 --out <file>

The file is the recorded trace ``tests/test_trace.py`` checks the reduction
on. It also prints what the trace holds (planes, lines, event counts, the
first events of each device line) and the reduction of the window.
"""
import argparse
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, trace
    harness.setup_process()
    from jax.profiler import ProfileData
    cell = harness.Cell.load(args.workload)
    harness.device_info(cell)
    session = harness.Session(cell, args.seed, t_start=T_START)
    trace_dir = harness.OUT_DIR / f"record-{cell.name}"
    harness.measure(session, args.seconds, trace_dir)
    path = trace.find_xplane(trace_dir)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(path, out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    for plane in ProfileData.from_file(str(out)).planes:
        lines = [(line.name, len(list(line.events))) for line in plane.lines]
        print("plane", plane.name, lines)
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                for ev in list(line.events)[:4]:
                    print("   ", line.name, "|", ev.name, ev.start_ns,
                          ev.duration_ns)
    devices, spans = trace.read_events(out)
    print(json.dumps(trace.reduce(devices, spans, cell.chips)))
    print("bytes", out.stat().st_size, "completed", len(session.done))
    return 0


if __name__ == "__main__":
    sys.exit(main())
