"""Multi-device training semantics checks (run with 8 fake host devices).

Covers: GSPMD sharded training, int8-compressed manual DP, elastic
checkpoint restore across mesh shapes, and pipeline parallelism.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.utils.compat import shard_map

from repro.configs.base import get_smoke_config
from repro.data import ShardedLoader, SyntheticCorpus
from repro.distributed.parallel import ParallelConfig, single_device_parallel
from repro.launch.mesh import auto_mesh
from repro.models.api import build_model
from repro.optim.compress import compressed_psum_int8
from repro.train import Trainer, TrainerConfig, TrainStepConfig
from repro.train.manual_dp import make_manual_dp_train_step
from repro.train.pipeline import make_pp_train_step
from repro.train.step import make_train_state


def check(name, cond):
    if not cond:
        print(f"FAIL {name}")
        sys.exit(1)
    print(f"OK {name}")


def gspmd_sharded_training():
    mesh = auto_mesh((4, 2), ("data", "model"))
    parallel = ParallelConfig(
        mesh=mesh, dp_axes=("data",), tp_axis="model", microbatches=2
    )
    cfg = get_smoke_config("qwen3_4b")
    bundle = build_model(cfg, parallel)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
    loader = ShardedLoader(corpus, batch_size=8, mesh=mesh, dp_axes=("data",))
    tr = Trainer(
        bundle, loader,
        TrainStepConfig(peak_lr=1e-3, warmup_steps=2, total_steps=12),
        TrainerConfig(total_steps=12, log_every=1),
        log_fn=lambda s: None,
    )
    out = tr.run()
    hist = out["history"]
    check("gspmd_loss_decreases", hist[-1]["loss"] < hist[0]["loss"])
    # params actually sharded (embed: vocab on model; d_model deliberately
    # NOT FSDP'd — see sharding.py §Perf iter 1 note)
    emb = tr.params["embed"]
    check("gspmd_params_sharded", emb.sharding.spec[0] == "model")
    wq = jax.tree.leaves(tr.params["layers"])  # some layer leaf is sharded
    check(
        "gspmd_layer_leaves_sharded",
        any(
            any(s is not None for s in l.sharding.spec)
            for l in wq if hasattr(l, "sharding")
        ),
    )
    return hist


def compressed_psum_close_to_exact():
    mesh = auto_mesh((8,), ("d",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 1000)), jnp.float32)

    def body(xl):
        flat = xl.reshape(-1)
        return (
            compressed_psum_int8(flat, ("d",)),
            jax.lax.pmean(flat, ("d",)),
        )

    comp, exact = jax.jit(
        shard_map(body, mesh=mesh, in_specs=(P("d"),), out_specs=(P(), P()),
                  check_vma=False)
    )(x)
    err = float(jnp.max(jnp.abs(comp - exact)))
    scale = float(jnp.max(jnp.abs(exact))) + 1e-9
    check("compressed_psum_close", err / scale < 0.05)


def manual_dp_with_compression():
    mesh = auto_mesh((8,), ("data",))
    parallel = ParallelConfig(
        mesh=mesh, dp_axes=("data",), tp_axis=None, grad_compression=True
    )
    cfg = dataclasses.replace(get_smoke_config("qwen3_4b"), num_layers=2)
    bundle = build_model(cfg, parallel)
    tcfg = TrainStepConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    params, opt = make_train_state(bundle, tcfg, jax.random.key(0))
    opt["ef_error"] = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.bfloat16), params
    )
    step = jax.jit(make_manual_dp_train_step(bundle, tcfg))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seq_len=32, seed=1)
    losses = []
    for i in range(8):
        batch = {"tokens": corpus.batch(i, 8)}
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    check("manual_dp_finite", np.isfinite(losses).all())
    check("manual_dp_loss_decreases", losses[-1] < losses[0])
    # int8 collectives really on the wire
    hlo = jax.jit(make_manual_dp_train_step(bundle, tcfg)).lower(
        params, opt, {"tokens": corpus.batch(0, 8)}
    ).compile().as_text()
    check("manual_dp_s8_collective", "s8[" in hlo and "all-to-all" in hlo)


def elastic_restore_across_meshes():
    from repro.checkpoint import CheckpointManager
    from repro.distributed import sharding as shd

    cfg = dataclasses.replace(get_smoke_config("qwen3_4b"), num_layers=2)
    tcfg = TrainStepConfig()

    mesh_a = auto_mesh((8,), ("data",))
    par_a = ParallelConfig(mesh=mesh_a, dp_axes=("data",), tp_axis=None)
    bundle_a = build_model(cfg, par_a)
    pshapes = bundle_a.param_shapes()
    specs_a = shd.param_pspecs(pshapes, par_a)
    sh_a = shd.to_named(mesh_a, specs_a)
    params_a = jax.jit(bundle_a.init, out_shardings=sh_a)(jax.random.key(7))

    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, async_write=False)
        m.save(1, {"params": params_a})

        mesh_b = auto_mesh((2, 4), ("data", "model"))
        par_b = ParallelConfig(mesh=mesh_b, dp_axes=("data",), tp_axis="model")
        specs_b = shd.param_pspecs(pshapes, par_b)
        sh_b = shd.to_named(mesh_b, specs_b)
        like = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), pshapes
        )
        _, tree, _ = m.restore({"params": like}, shardings={"params": sh_b})
        params_b = tree["params"]
        same = jax.tree.map(
            lambda a, b: np.allclose(np.asarray(a), np.asarray(b)),
            params_a, params_b,
        )
        check("elastic_restore_values", all(jax.tree.leaves(same)))
        emb_spec = params_b["embed"].sharding.spec
        check("elastic_restore_resharded", emb_spec == specs_b["embed"])


def pipeline_parallel_matches_single_device():
    cfg = dataclasses.replace(
        get_smoke_config("qwen3_4b"), num_layers=4, dtype="float32"
    )
    tcfg = TrainStepConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seq_len=16, seed=2)
    batch = {"tokens": corpus.batch(0, 8)}

    # single-device reference loss
    bundle_ref = build_model(cfg, single_device_parallel())
    params = bundle_ref.init(jax.random.key(9))
    loss_ref, _ = bundle_ref.loss(params, batch)

    mesh = auto_mesh((2,), ("stage",))
    par = ParallelConfig(mesh=mesh, dp_axes=(), tp_axis=None)
    bundle_pp = build_model(cfg, par)
    from repro.optim import adamw_init

    opt = adamw_init(params, tcfg.adamw)
    step = jax.jit(make_pp_train_step(bundle_pp, tcfg, num_microbatches=4))
    p2, o2, metrics = step(params, opt, batch)
    check(
        "pp_loss_matches_reference",
        abs(float(metrics["loss"]) - float(loss_ref)) < 5e-3,
    )
    losses = [float(metrics["loss"])]
    for i in range(1, 6):
        p2, o2, metrics = step(p2, o2, {"tokens": corpus.batch(i, 8)})
        losses.append(float(metrics["loss"]))
    check("pp_loss_decreases", losses[-1] < losses[0])


def main():
    gspmd_sharded_training()
    compressed_psum_close_to_exact()
    manual_dp_with_compression()
    elastic_restore_across_meshes()
    pipeline_parallel_matches_single_device()
    print("ALL_OK")


if __name__ == "__main__":
    main()
