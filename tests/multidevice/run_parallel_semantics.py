"""Parallel-semantics checks: MoE EP ≡ dense, distributed dedup ≡ local,
sequence-sharded decode ≡ single-device decode (8 fake devices)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_smoke_config
from repro.core.table import DistributedHashTable
from repro.data import dedup_mask, dedup_mask_distributed
from repro.distributed.parallel import ParallelConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import auto_mesh
from repro.models import moe as moe_mod
from repro.models.api import build_model


def check(name, cond):
    if not cond:
        print(f"FAIL {name}")
        sys.exit(1)
    print(f"OK {name}")


def moe_ep_matches_dense():
    """The paper's exchange as MoE dispatch: EP output == dense output."""
    cfg = dataclasses.replace(
        get_smoke_config("mixtral_8x22b"), dtype="float32", num_experts=4,
        moe_capacity_factor=4.0,  # generous: no drops → exact equality
    )
    mesh = auto_mesh((8,), ("data",))
    parallel = ParallelConfig(
        mesh=mesh, dp_axes=("data",), tp_axis=None, moe_impl="ep"
    )
    key = jax.random.key(0)
    params = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.key(1), (8, 16, cfg.d_model), jnp.float32)

    dense_out, dense_aux = jax.jit(
        lambda p, xx: moe_mod.moe_dense(p, xx, cfg)
    )(params, x)
    ep_out, ep_aux = jax.jit(
        lambda p, xx: moe_mod.moe_ep(p, xx, cfg, parallel)
    )(params, x)
    err = float(jnp.max(jnp.abs(dense_out - ep_out)))
    check("moe_ep_matches_dense", err < 1e-4)
    # aux is pmean of per-shard stats, close but not identical; sanity only
    check("moe_ep_aux_finite", np.isfinite(float(ep_aux)))


def distributed_dedup_matches_local():
    rng = np.random.default_rng(0)
    base = rng.integers(1, 1 << 20, size=(48, 16)).astype(np.int32)
    toks = np.concatenate([base, base[:16]])  # 16 duplicate rows
    local = np.asarray(dedup_mask(jnp.asarray(toks)))

    mesh = auto_mesh((8,), ("d",))
    table = DistributedHashTable(mesh, ("d",), hash_range=256)
    dist = np.asarray(dedup_mask_distributed(table, jnp.asarray(toks)))
    check("distributed_dedup_matches_local", (local == dist).all())
    check("dedup_finds_duplicates", (~local).sum() == 16)


def seq_sharded_decode_matches_single():
    """kv_heads=1 cache sequence-sharded over 'model': decode must equal
    the unsharded result (GSPMD inserts the flash-decode style combine)."""
    cfg = dataclasses.replace(
        get_smoke_config("granite_20b"), dtype="float32", num_layers=2
    )
    mesh = auto_mesh((2, 4), ("data", "model"))
    parallel = ParallelConfig(mesh=mesh, dp_axes=("data",), tp_axis="model")
    bundle = build_model(cfg, parallel)
    params = bundle.init(jax.random.key(3))

    b, cache_len = 4, 64
    rng = np.random.default_rng(4)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, 8), np.int32))
    logits_p, caches = bundle.prefill(
        params, {"tokens": prompt}, cache_len=cache_len
    )
    tok = jnp.argmax(logits_p, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((b,), 8, jnp.int32)

    ref_logits, _ = jax.jit(bundle.decode_step)(params, caches, tok, pos)

    cache_shapes = jax.eval_shape(lambda: caches)
    cspecs = shd.cache_pspecs(cache_shapes, parallel)
    flat_specs = jax.tree.leaves(cspecs, is_leaf=lambda s: isinstance(s, P))
    has_seq_shard = any(
        len(s) >= 4 and s[3] == "model" for s in flat_specs
    )
    check("granite_cache_seq_sharded", has_seq_shard)
    sh = shd.to_named(mesh, cspecs)
    caches_sharded = jax.tree.map(jax.device_put, caches, sh)
    got_logits, _ = jax.jit(bundle.decode_step)(params, caches_sharded, tok, pos)
    err = float(jnp.max(jnp.abs(got_logits - ref_logits)))
    check("seq_sharded_decode_matches", err < 1e-4)


def main():
    moe_ep_matches_dense()
    distributed_dedup_matches_local()
    seq_sharded_decode_matches_single()
    print("ALL_OK")


if __name__ == "__main__":
    main()
