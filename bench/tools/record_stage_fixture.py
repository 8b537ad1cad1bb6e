"""Record the small device trace that the stage-reduction tests read.

    python bench/tools/record_stage_fixture.py OUT_DIR

Runs two tiny jitted programs named like the table's executors
(``exec_query``, ``exec_join``) whose steps carry the device stages'
``jax.named_scope`` names, one of them opened inside a loop's body, under
the JAX profiler.  Each ``exec_join`` call runs inside a host span of a
``repro.obs.tracing.Tracer`` (``plan.join``) that dispatches at once (so
the span's start dates the dispatch) and sleeps 4 ms after the result, so
the idle gap after the call's device work lies under a program span.  It
writes ``OUT_DIR/stages.xplane.pb`` and ``OUT_DIR/stages_hlo.json`` (the
optimized HLO text of each program, by jit name).  Run it on the chip: a
trace recorded on the CPU has no device plane.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src")]


def main(out: Path) -> int:
    import jax
    import jax.numpy as jnp

    from repro.obs.tracing import Tracer

    @jax.jit
    def exec_query(x):
        with jax.named_scope("route"):
            h = (x * 7 + 3) % x.shape[0]
        with jax.named_scope("return"):
            return jnp.sort(h) + 1

    @jax.jit
    def exec_join(x, y):
        with jax.named_scope("route"):
            k = (x * 7 + 3) % x.shape[0]
        with jax.named_scope("locate"):

            def body(i, acc):
                with jax.named_scope("gather"):  # a scope inside the loop
                    return acc + jnp.take(y, (k + i * 31) % y.shape[0])

            acc = jax.lax.fori_loop(0, 8, body, jnp.zeros_like(x))
        with jax.named_scope("expand"):
            return jnp.sort(acc) * 2

    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    y = jnp.arange(1 << 20, dtype=jnp.int32)
    hlo = {
        "exec_query": exec_query.lower(x).compile().as_text(),
        "exec_join": exec_join.lower(x, y).compile().as_text(),
    }
    exec_query(x).block_until_ready()
    exec_join(x, y).block_until_ready()
    tracer = Tracer()
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # as the harness traces
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.host_gap"):
                time.sleep(0.01)
            for _ in range(3):
                exec_query(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.host_gap"):
                    time.sleep(0.004)
            for _ in range(3):
                with tracer.span("plan.join"):
                    exec_join(x, y).block_until_ready()
                    time.sleep(0.004)
            with jax.profiler.TraceAnnotation("bench.host_gap"):
                time.sleep(0.01)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
        shutil.copy(path, out / "stages.xplane.pb")
    (out / "stages_hlo.json").write_text(json.dumps(hlo))
    print(f"device_kind {jax.devices()[0].device_kind}; wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
