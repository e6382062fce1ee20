"""Mesh / sharding / sharded-step tests on the 8-device virtual CPU mesh.

Validates the same thing the driver's ``dryrun_multichip`` does: real
tp/dp/sp/ep shardings compile and execute, and sharded results match the
single-device reference numerically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.models.config import get_preset
from fusioninfer_tpu.models.transformer import forward, init_params
from fusioninfer_tpu.parallel import (
    MeshConfig,
    build_mesh,
    infer_mesh_config,
    make_forward,
    make_train_step,
    param_specs,
    shard_params,
    sharded_init,
    single_device_mesh,
)

CFG = get_preset("qwen3-tiny")


def assert_logits_close(ref, out, tol=0.05, frac=0.995, argmax_frac=0.95):
    """bf16 sharded vs unsharded compare: reassociated reductions shift a
    tail of elements beyond any tight elementwise bound, so require (a)
    almost all elements within tolerance and (b) argmax agreement."""
    ref = np.asarray(ref, np.float32)
    out = np.asarray(out, np.float32)
    ok = np.abs(ref - out) <= tol + 0.05 * np.abs(ref)
    assert ok.mean() >= frac, f"only {ok.mean():.4f} of elements within tolerance"
    agree = (ref.argmax(-1) == out.argmax(-1)).mean()
    assert agree >= argmax_frac, f"argmax agreement {agree:.4f}"


def test_mesh_config_validate():
    MeshConfig(dp=2, tp=4).validate(8)
    with pytest.raises(ValueError):
        MeshConfig(dp=2, tp=2).validate(8)
    with pytest.raises(ValueError):
        MeshConfig(dp=0).validate()


def test_infer_mesh_config_defaults_to_tp():
    cfg = infer_mesh_config(8)
    assert cfg.tp == 8 and cfg.dp == 1
    cfg = infer_mesh_config(8, tp=2, sp=2)
    assert (cfg.dp, cfg.sp, cfg.ep, cfg.tp) == (2, 2, 1, 2)
    with pytest.raises(ValueError):
        infer_mesh_config(8, tp=3)
    with pytest.raises(ValueError):
        infer_mesh_config(4, sp=8)  # sp alone exceeds device count


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig(dp=2, sp=1, ep=1, tp=4))
    assert mesh.axis_names == ("dp", "sp", "ep", "tp")
    assert mesh.devices.shape == (2, 1, 1, 4)


def test_param_specs_congruent_with_params():
    key = jax.random.PRNGKey(0)
    params = init_params(CFG, key)
    specs = param_specs(CFG)
    # identical tree structure
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )


def test_moe_param_specs_congruent():
    cfg = get_preset("moe-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0))
    specs = param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )


def test_sharded_forward_matches_single_device():
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    key = jax.random.PRNGKey(1)
    params = init_params(CFG, key)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, CFG.vocab_size)

    ref = forward(CFG, params, tokens)

    sharded = shard_params(CFG, mesh, params)
    fwd = make_forward(CFG, mesh)
    out = fwd(sharded, jax.device_put(tokens, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("dp", "sp"))))
    assert_logits_close(ref, out)


def test_sharded_init_lands_sharded():
    mesh = build_mesh(MeshConfig(tp=8))
    params = sharded_init(CFG, mesh, jax.random.PRNGKey(0))
    wq = params["layers"]["wq"]
    # column-parallel: last axis split 8 ways
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {(CFG.n_layers, CFG.d_model, CFG.n_heads * CFG.head_dim // 8)}


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_sharded_kv_cache_is_born_sharded(kv_dtype):
    """The pool is sized per device, so it must never exist whole on
    one: every leaf comes back with its KV-head axis split over tp and
    each device holding only its heads (on the chip the unsharded
    build-then-device_put exhausted device 0 at tp=4)."""
    from fusioninfer_tpu.engine.kv_cache import CacheConfig, init_kv_cache
    from fusioninfer_tpu.parallel.sharding import sharded_kv_cache

    mesh = build_mesh(MeshConfig(tp=2), jax.devices()[:2])
    cc = CacheConfig(n_pages=9, page_size=8, max_pages_per_seq=4,
                     kv_dtype=kv_dtype)
    cache = sharded_kv_cache(CFG, cc, mesh)
    whole = jax.eval_shape(lambda: init_kv_cache(CFG, cc))
    assert set(cache) == set(whole)
    for name, leaf in cache.items():
        assert leaf.shape == whole[name].shape
        assert leaf.dtype == whole[name].dtype
        shards = leaf.addressable_shards
        assert len(shards) == 2
        for shard in shards:
            want = list(leaf.shape)
            want[1] //= 2  # [L, KV/tp, ...]
            assert list(shard.data.shape) == want
        assert not np.asarray(leaf).any()


def test_train_step_runs_and_descends():
    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    params = sharded_init(CFG, mesh, jax.random.PRNGKey(0))
    init_state, train_step = make_train_step(CFG, mesh)
    opt_state = init_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0, CFG.vocab_size)

    losses = []
    for _ in range(3):
        params, opt_state, loss = train_step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0], f"loss did not descend: {losses}"


def test_single_device_mesh_works():
    mesh = single_device_mesh()
    params = sharded_init(CFG, mesh, jax.random.PRNGKey(0))
    fwd = make_forward(CFG, mesh)
    tokens = jnp.zeros((1, 8), jnp.int32)
    out = fwd(params, tokens)
    assert out.shape == (1, 8, CFG.vocab_size)


def test_moe_sharded_forward_over_ep():
    cfg = get_preset("moe-tiny")
    mesh = build_mesh(MeshConfig(dp=1, sp=1, ep=2, tp=4))
    params = init_params(cfg, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, cfg.vocab_size)
    ref = forward(cfg, params, tokens)
    sharded = shard_params(cfg, mesh, params)
    out = make_forward(cfg, mesh)(sharded, tokens)
    assert_logits_close(ref, out)
