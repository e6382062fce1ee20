"""Model correctness on the CPU mesh: shapes, causality, GQA, QK-norm,
MoE, and a gradient step reducing loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.models.config import ModelConfig, get_preset, list_presets
from fusioninfer_tpu.models.transformer import forward, init_params, loss_fn


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("qwen3-tiny")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def test_presets_cover_baseline_models():
    assert {"qwen3-tiny", "qwen3-8b", "qwen3-1.7b", "llama3-70b", "moe-tiny"} <= set(list_presets())


def test_forward_shapes_and_dtype(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits = forward(cfg, params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality(tiny):
    """Perturbing a future token must not change past logits."""
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(2), (1, 12), 0, cfg.vocab_size)
    base = forward(cfg, params, tokens)
    perturbed = tokens.at[0, 8].set((tokens[0, 8] + 1) % cfg.vocab_size)
    out = forward(cfg, params, perturbed)
    np.testing.assert_allclose(np.asarray(base[0, :8]), np.asarray(out[0, :8]), rtol=1e-5)
    assert not np.allclose(np.asarray(base[0, 8:]), np.asarray(out[0, 8:]))


def test_moe_forward_and_expert_mixing():
    cfg = get_preset("moe-tiny")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    logits = forward(cfg, params, tokens)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_untied_head_used():
    cfg = ModelConfig(name="untied", tie_embeddings=False)
    params = init_params(cfg, jax.random.key(0))
    assert "lm_head" in params
    tokens = jnp.zeros((1, 4), jnp.int32)
    base = forward(cfg, params, tokens)
    params2 = dict(params, lm_head=params["lm_head"] * 0.0)
    out = forward(cfg, params2, tokens)
    assert not np.allclose(np.asarray(base), np.asarray(out))


def test_gradient_step_reduces_loss(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(3), (4, 32), 0, cfg.vocab_size)
    loss0, grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, tokens))(params)
    params1 = jax.tree.map(lambda p, g: p - 0.5 * g.astype(p.dtype), params, grads)
    loss1 = loss_fn(cfg, params1, tokens)
    assert float(loss1) < float(loss0)
    # random init: loss near ln(V)
    assert abs(float(loss0) - np.log(cfg.vocab_size)) < 1.5


class TestExpertLayer:
    """The ONE expert layer (``moe_layer``): sorted assignments through a
    grouped product, no capacity, nothing dropped.  It must agree with the
    exact dense formulation (``moe_ffn``, every expert on every token) at
    every expert count, token count and skew: the cases the capacity
    dispatch it replaced either matched only "with ample capacity" or
    dropped assignments in."""

    def _weights(self, E=8, D=16, F=32, seed=0):
        ks = jax.random.split(jax.random.key(seed), 4)
        router = jax.random.normal(ks[0], (D, E), jnp.float32)
        w_gate = jax.random.normal(ks[1], (E, D, F), jnp.float32) / 4
        w_up = jax.random.normal(ks[2], (E, D, F), jnp.float32) / 4
        w_down = jax.random.normal(ks[3], (E, F, D), jnp.float32) / 4
        return router, w_gate, w_up, w_down

    def _cfg(self, E, k):
        return ModelConfig(
            name="moe-case", d_model=16, n_experts=E, n_experts_active=k,
            moe_d_ff=32, dtype="float32").validate()

    @pytest.mark.parametrize("E, k, T, seed", [
        (8, 2, 12, 9),     # was: matches dense "with ample capacity"
        (8, 2, 64, 3),     # was: tight capacity 0.5 dropped; now exact
        (32, 4, 40, 1),    # was: past the 16-expert switch, capacity path
        (128, 8, 1, 2),    # was: the capacity floor for one decode token
        (128, 8, 96, 4),   # qwen3-30b-a3b's router shape, a chunk of tokens
        (4, 2, 7, 5),      # moe-tiny's
    ], ids=["ample", "tight", "past-16-experts", "one-decode-token",
            "128-experts-chunk", "moe-tiny"])
    def test_matches_the_dense_formulation_with_nothing_dropped(
            self, E, k, T, seed):
        from fusioninfer_tpu.models.transformer import moe_ffn, moe_layer

        router, g, u, d = self._weights(E=E)
        x = jax.random.normal(jax.random.key(seed), (T, 16), jnp.float32)
        dense = moe_ffn(x, router, g, u, d, n_active=k)
        layer = {"router": router, "w_gate": g, "w_up": u, "w_down": d}
        got, stats = moe_layer(self._cfg(E, k), layer, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(dense), atol=1e-4, rtol=1e-4)
        # every assignment is local and computed: none dropped
        assert stats.tolist()[:2] == [T * k, T * k] and int(stats[3]) == 1
        assert 1 <= int(stats[2]) <= min(E, T * k)

    def test_a_skewed_router_drops_nothing(self):
        """64 tokens that all choose the same two of 8 experts: 8 times
        the even load on each.  A 2.0x capacity kept a quarter of them."""
        from fusioninfer_tpu.models.transformer import moe_ffn, moe_layer

        router, g, u, d = self._weights()
        router = jnp.zeros_like(router).at[0, 3].set(9.0).at[0, 6].set(7.0)
        x = jnp.abs(jax.random.normal(jax.random.key(3), (64, 16))) + 0.1
        layer = {"router": router, "w_gate": g, "w_up": u, "w_down": d}
        got, stats = moe_layer(self._cfg(8, 2), layer, x)
        assert stats.tolist() == [128, 128, 2, 1, 0]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(moe_ffn(x, router, g, u, d, 2)),
            atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("n_experts", [4, 32, 128])
    def test_every_expert_count_serves_through_the_one_layer(self, n_experts):
        from fusioninfer_tpu.models import transformer

        cfg = ModelConfig(
            name="moe-many", vocab_size=128, d_model=32, n_layers=2,
            n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
            n_experts=n_experts, n_experts_active=4, moe_d_ff=32,
            dtype="float32", attn_impl="reference",
        ).validate()
        for gone in ("moe_ffn_sparse", "moe_capacity", "DENSE_MOE_MAX_EXPERTS"):
            assert not hasattr(transformer, gone)
        params = init_params(cfg, jax.random.key(0))
        logits = forward(cfg, params, jnp.asarray([[1, 2, 3, 4]]))
        assert logits.shape == (1, 4, 128)
        assert bool(jnp.isfinite(logits).all())

    def test_no_moe_preset_has_a_capacity(self):
        import inspect

        from fusioninfer_tpu.models.transformer import moe_layer

        assert "capacity" not in " ".join(
            inspect.signature(moe_layer).parameters)
        for name in list_presets():
            cfg = get_preset(name)
            if cfg.is_moe:
                assert 1 <= cfg.experts_held <= cfg.n_experts
