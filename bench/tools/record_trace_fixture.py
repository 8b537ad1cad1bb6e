"""Record the small device trace that the trace-reduction tests read.

    python bench/tools/record_trace_fixture.py OUT_DIR

Runs two tiny jitted programs named like the table's executors
(``exec_query``, ``exec_join``) a few times each, with host spans and
deliberate host gaps between them, under the JAX profiler, and copies the
resulting ``.xplane.pb`` to ``OUT_DIR/fixture.xplane.pb``.  It also writes
``OUT_DIR/fixture_layout.txt``: every plane and line of the trace, with the
first events of each, so the layout can be read by hand.  Run it on the chip:
a trace recorded on the CPU has no device plane.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path


def main(out: Path) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    @jax.jit
    def exec_query(x):
        return jnp.sort(x) + 1

    @jax.jit
    def exec_join(x, y):
        return jnp.take(y, jnp.argsort(x)) * 2

    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    y = jnp.arange(1 << 20, dtype=jnp.int32)
    exec_query(x).block_until_ready()
    exec_join(x, y).block_until_ready()
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # as the harness traces
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.window"):
            # gaps at both edges: the device clock runs about 1 ms off the
            # host's, and no call should straddle the window's edge
            with jax.profiler.TraceAnnotation("bench.host_gap"):
                time.sleep(0.01)
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    exec_query(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.host_gap"):
                    time.sleep(0.004)
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.call"):
                    exec_join(x, y).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_gap"):
                time.sleep(0.01)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
        shutil.copy(path, out / "fixture.xplane.pb")
    lines = [f"device_kind {jax.devices()[0].device_kind}"]
    for plane in ProfileData.from_file(str(out / "fixture.xplane.pb")).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:6]:
                stats = {k: v for k, v in e.stats}
                lines.append(
                    f"    {e.name!r} start_ns={e.start_ns} dur_ns={e.duration_ns} {stats}"
                )
    (out / "fixture_layout.txt").write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
