"""Record the small device trace that checks ``benchmark/trace.py``.

Runs a fixed pattern on one device under the JAX profiler, with the
profiler options the benchmark uses: a slice mark, a sleep of GAP_S,
LAUNCHES launches of one jitted matmul chain, each waited for, with a
sleep of GAP_S between them, a sleep of GAP_S and a slice mark.  The
device is busy in LAUNCHES bursts and idle in between, so the reduction
must find LAUNCHES + 1 idle gaps of at least GAP_S in the slice.

    python benchmark/recorded/record.py OUT_DIR [--dump]

writes ``OUT_DIR/plugins/profile/<run>/*.xplane.pb``, and with
``--dump`` prints each plane, its lines and the first events of each,
so that the trace's layout can be read by eye.  The file kept beside
this script is one such recording, made on a TPU v5e.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])

from benchmark.trace import MARK  # noqa: E402

LAUNCHES = 5
GAP_S = 0.05
N = 4096
REPEAT = 8


def dump(path: Path) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"{t0:.0f} .. {t1:.0f} ns")
            for e in evs[:4]:
                print(f"    {e.name[:90]!r} start {e.start_ns:.0f} "
                      f"dur {e.duration_ns:.0f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out")
    ap.add_argument("--dump", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record.py: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 1

    @jax.jit
    def chain(x):
        for _ in range(REPEAT):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((N, N), jnp.bfloat16) / N
    chain(x).block_until_ready()  # compile outside the trace
    out = Path(args.out)
    clocks = {"wall_ns_before": time.time_ns(),
              "monotonic_ns_before": time.monotonic_ns()}
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation(MARK):
        time.sleep(GAP_S)
    for i in range(LAUNCHES):
        chain(x).block_until_ready()
        if i < LAUNCHES - 1:
            time.sleep(GAP_S)
    time.sleep(GAP_S)
    with jax.profiler.TraceAnnotation(MARK):
        pass
    jax.profiler.stop_trace()
    clocks.update(wall_ns_after=time.time_ns(),
                  monotonic_ns_after=time.monotonic_ns())
    print(json.dumps({"clocks": clocks, "device_kind": dev.device_kind}))
    if args.dump:
        for p in sorted(out.rglob("*.xplane.pb")):
            print(f"{p} ({p.stat().st_size} bytes)")
            dump(p)
    return 0


if __name__ == "__main__":
    sys.exit(main())
