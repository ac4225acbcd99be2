"""Ops tests: flash attention vs XLA reference, fused loss semantics."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.ops import flash_attention as _fa
from luminaai_tpu.ops import fused as _fused
from luminaai_tpu.ops.fused import clip_by_global_norm, global_norm


# Every call below runs as ONE jitted program: eager dispatch compiles
# each primitive (and each interpreted Pallas grid step) on its own,
# which cost 4-14 s a test on one core. Options are bound statically.
def flash_attention(q, k, v, **kw):
    return jax.jit(functools.partial(_fa.flash_attention, **kw))(q, k, v)


def cross_entropy_loss(logits, labels, loss_mask=None, loss_weights=None, **kw):
    return jax.jit(functools.partial(_fused.cross_entropy_loss, **kw))(
        logits, labels, loss_mask, loss_weights
    )


def _grad3(f):
    return jax.jit(jax.grad(f, argnums=(0, 1, 2)))


def ref_attention(q, k, v, causal=True, window=None):
    B, S, Hq, D = q.shape
    g = Hq // k.shape[2]
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        if window is not None:
            pos = jnp.arange(S)
            mask = jnp.logical_and(
                mask, pos[:, None] - pos[None, :] < window
            )
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


class TestFlashAttention:
    @pytest.mark.parametrize("hkv", [4, 2, 1], ids=["mha", "gqa", "mqa"])
    def test_forward_matches_reference(self, hkv):
        B, S, Hq, D = 2, 256, 4, 128
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, hkv, D), jnp.float32)
        out = flash_attention(q, k, v, block_q=128, block_kv=128)
        ref = jax.jit(ref_attention)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_backward_matches_reference(self):
        B, S, Hq, Hkv, D = 1, 256, 2, 1, 128
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        f = lambda q, k, v: (flash_attention(q, k, v, block_q=128, block_kv=128) ** 2).sum()
        r = lambda q, k, v: (ref_attention(q, k, v) ** 2).sum()
        gf = _grad3(f)(q, k, v)
        gr = _grad3(r)(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_non_causal(self):
        B, S, H, D = 1, 128, 2, 128
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
        out = flash_attention(q, k, v, causal=False, block_q=128, block_kv=128)
        ref = ref_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("window", [64, 128, 200])
    def test_sliding_window_fwd_and_bwd(self, window):
        """Windowed attention: position i attends to [i-W+1, i] only.
        Block-skip geometry differs per W vs the 128-blocks (sub-block,
        exact-block, straddling) — all must match the masked reference,
        grads included."""
        B, S, Hq, Hkv, D = 1, 512, 2, 1, 128
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        out = flash_attention(
            q, k, v, block_q=128, block_kv=128, window=window
        )
        ref = ref_attention(q, k, v, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

        f = lambda q, k, v: (
            flash_attention(q, k, v, block_q=128, block_kv=128,
                            window=window) ** 2
        ).sum()
        r = lambda q, k, v: (ref_attention(q, k, v, window=window) ** 2).sum()
        gf = _grad3(f)(q, k, v)
        gr = _grad3(r)(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_window_changes_result(self):
        # Guard against the mask silently not applying: a tight window
        # must differ from full causal.
        B, S, H, D = 1, 256, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
        full = flash_attention(q, k, v, block_q=128, block_kv=128)
        win = flash_attention(q, k, v, block_q=128, block_kv=128, window=32)
        assert float(jnp.max(jnp.abs(full - win))) > 1e-3


class TestCrossEntropy:
    def test_matches_naive(self):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(rng, (2, 8, 16))
        labels = jax.random.randint(rng, (2, 8), 0, 16)  # lumina: disable=LX005 -- independent-enough draws for a loss identity test
        loss, _ = cross_entropy_loss(logits, labels)
        naive = -jnp.take_along_axis(
            jax.nn.log_softmax(logits, -1), labels[..., None], -1
        ).mean()
        assert float(loss) == pytest.approx(float(naive), abs=1e-5)

    def test_mask_excludes_tokens(self):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(rng, (1, 4, 8))
        labels = jnp.array([[1, 2, 3, 4]])
        mask = jnp.array([[1.0, 1.0, 0.0, 0.0]])
        loss_m, m = cross_entropy_loss(logits, labels, loss_mask=mask)
        loss_half, _ = cross_entropy_loss(logits[:, :2], labels[:, :2])
        assert float(loss_m) == pytest.approx(float(loss_half), abs=1e-5)
        assert float(m["tokens_in_loss"]) == 2.0

    def test_assistant_weighting(self):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(rng, (1, 4, 8))
        labels = jnp.array([[1, 2, 3, 4]])
        w = jnp.array([[1.0, 1.0, 1.5, 1.5]])
        loss_w, _ = cross_entropy_loss(logits, labels, loss_weights=w)
        # weighted mean, not plain mean
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), labels[..., None], -1)[..., 0]
        expected = float((nll * w).sum() / w.sum())
        assert float(loss_w) == pytest.approx(expected, abs=1e-5)

    def test_z_loss_positive(self):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(rng, (1, 4, 8)) * 5
        labels = jnp.zeros((1, 4), jnp.int32)
        loss_z, m = cross_entropy_loss(logits, labels, z_loss_weight=1e-2)
        loss, _ = cross_entropy_loss(logits, labels)
        assert float(loss_z) > float(loss)
        assert float(m["z_loss"]) > 0


class TestGradClip:
    def test_clip(self):
        grads = {"a": jnp.full((4,), 10.0), "b": jnp.full((3,), -10.0)}
        clipped, norm = clip_by_global_norm(grads, max_norm=1.0)
        assert float(norm) == pytest.approx(np.sqrt(700.0), rel=1e-5)
        assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-4)

    def test_no_clip_below_threshold(self):
        grads = {"a": jnp.array([0.1, 0.1])}
        clipped, norm = clip_by_global_norm(grads, max_norm=1.0)
        np.testing.assert_allclose(np.asarray(clipped["a"]), [0.1, 0.1], rtol=1e-5)


class TestFusedLMHeadCE:
    """Chunked fused LM-head CE must be numerically identical to the
    unfused decode→CE path (it replaces it by default)."""

    def _setup(self, B=2, S=64, H=32, V=97, seed=0):
        from luminaai_tpu.ops.fused import fused_lm_head_cross_entropy

        rng = np.random.RandomState(seed)
        hidden = jnp.asarray(rng.randn(B, S, H), jnp.float32)
        emb = jnp.asarray(rng.randn(V, H) * 0.05, jnp.float32)
        labels = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
        mask = jnp.asarray(rng.rand(B, S) > 0.3, jnp.float32)
        weights = jnp.asarray(rng.rand(B, S) + 0.5, jnp.float32)
        return fused_lm_head_cross_entropy, hidden, emb, labels, mask, weights

    def test_matches_unfused_with_grads(self):
        fused_fn, hidden, emb, labels, mask, weights = self._setup()

        def plain(h, e):
            logits = jnp.einsum("bsh,vh->bsv", h, e)
            return cross_entropy_loss(
                logits, labels, mask, weights,
                z_loss_weight=1e-3, label_smoothing=0.1,
            )[0]

        def fused(h, e):
            return fused_fn(
                h, e, labels, mask, weights,
                z_loss_weight=1e-3, label_smoothing=0.1, chunk_size=16,
            )[0]

        np.testing.assert_allclose(
            float(jax.jit(plain)(hidden, emb)),
            float(jax.jit(fused)(hidden, emb)), atol=2e-6
        )
        gp = jax.jit(jax.grad(plain, argnums=(0, 1)))(hidden, emb)
        gf = jax.jit(jax.grad(fused, argnums=(0, 1)))(hidden, emb)
        for a, b in zip(gp, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_metrics_parity_and_odd_chunk(self):
        fused_fn, hidden, emb, labels, mask, weights = self._setup()
        logits = jnp.einsum("bsh,vh->bsv", hidden, emb)
        _, m_plain = cross_entropy_loss(logits, labels, mask, weights)
        # chunk_size not dividing S falls back to the largest divisor.
        _, m_fused = jax.jit(functools.partial(fused_fn, chunk_size=23))(
            hidden, emb, labels, mask, weights
        )
        for key in ("ce_loss", "tokens_in_loss", "total_loss"):
            np.testing.assert_allclose(
                float(m_plain[key]), float(m_fused[key]), rtol=1e-5
            )


def test_windowed_grid_is_banded():
    """The windowed kernels must shrink the sliding grid axis (O(S·W) grid
    steps + K/V DMA, not O(S²)) — the whole point of the banded index
    maps. Pin the step-count math."""
    from luminaai_tpu.ops.flash_attention import _n_kv_steps, _n_q_steps

    # window 1024, blocks 512: band spans at most 4 kv blocks per q block.
    assert _n_kv_steps(131072, 512, 512, 1024) == 4
    assert _n_q_steps(131072, 512, 512, 1024) == 4
    # windowless: full grid.
    assert _n_kv_steps(131072, 512, 512, 0) == 256
    # window >= seq: no shrink beyond the full grid.
    assert _n_kv_steps(2048, 512, 512, 4096) == 4
