"""How the held experts' sorted rows go back to their tokens
(models/moe.py `_held_combine`): one product on the MXU at a tick's sizes,
a float32 scatter-add at a training step's, chosen by a function of the
shapes alone (`held_combine_is_product`).

The two forms are held to each other at a served tick's shape cut down
(T 36 token rows, R 288 sorted rows, H 256, top-8; 12 of 384 and 16 of 128
experts held, as kimi-k2 and command-a-plus hold theirs): the float32 sums
before the final cast, the cast output, the gradients, and what a grouped
matmul that leaves NaN past its rows does to either. The gauge
`moe_held_combine_product` says which form a built program has.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models import moe

T, H, F, K = 36, 256, 64, 8
# (experts, held offset, held count, capacity_factor): R = cf * T * K *
# held / experts = 288 in both, every pair of the tick (dropless).
SHARES = {
    "12_of_384": (384, 0, 12, 32.0),
    "16_of_128": (128, 112, 16, 8.0),
}
RULE = dict(select_bias=None, renormalize=True, scale=1.0)


def _probs(share, routing, seed=3):
    """Router scores [1, T, E] whose top-8 are shaped by `routing`:
    'mixed' (token 0 picks held experts alone, token 1 none, the others a
    few), 'none' (no pair on a held expert: total = 0), 'all' (every pair
    on one: total = R)."""
    E, off, cnt, _ = SHARES[share]
    p = jax.random.uniform(jax.random.key(seed), (1, T, E), minval=0.05,
                           maxval=0.5)
    here = (jnp.arange(E) >= off) & (jnp.arange(E) < off + cnt)
    if routing == "none":
        return jnp.where(here, 0.0, p)
    if routing == "all":
        return jnp.where(here, p + 1.0, p)
    # A third of the held experts drawn high: a few picks a token.
    lift = here & (jnp.arange(E) % 3 == 0)
    p = jnp.where(lift, p + 0.3 * jax.random.uniform(
        jax.random.key(seed + 1), (1, T, E)), p)
    p = p.at[0, 0].set(jnp.where(here, p[0, 0] + 1.0, p[0, 0]))
    return p.at[0, 1].set(jnp.where(here, 0.0, p[0, 1]))


def _operands(share, dtype, seed=5):
    E, off, cnt, _ = SHARES[share]
    kx, ki, ko = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(kx, (1, T, H), jnp.float32)
    wi = 0.08 * jax.random.normal(ki, (cnt, H, 2 * F), jnp.float32)
    wo = 0.15 * jax.random.normal(ko, (cnt, F, H), jnp.float32)
    return x.astype(dtype), wi.astype(dtype), wo.astype(dtype)


def _held(share, probs, x, wi, wo, dtype, live=None, gmm_fn=None):
    E, off, cnt, cf = SHARES[share]
    rows = int(cf * T * K * cnt / E)
    assert rows == 288
    return moe._gmm_held(
        x, probs, wi, wo, top_k=K, num_experts=E, offset=off,
        row_bound=rows, dtype=dtype, gmm_fn=gmm_fn or moe._pick_gmm(),
        rule=RULE, live=live,
    )


def _both_forms(monkeypatch, run):
    """`run()` under each form: {form: (what it returned, the float32
    sums _held_combine gave before the final cast)}."""
    combine, seen = moe._held_combine, {}
    for form in (True, False):
        sums = []

        def spy(*args):
            assert args[-1] is form
            sums.append(combine(*args))
            return sums[-1]

        monkeypatch.setattr(moe, "held_combine_is_product",
                            lambda *shape: form)
        monkeypatch.setattr(moe, "_held_combine", spy)
        seen[form] = (run(), sums[0])
    return seen[True], seen[False]


def _live_mask():
    # Token 0 (every pick held) stays live, token 3 and a run of chunk
    # padding do not.
    return jnp.ones((1, T), bool).at[0, 3].set(False).at[0, 30:].set(False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("with_live", [False, True], ids=["all_rows", "live"])
@pytest.mark.parametrize("routing", ["mixed", "none", "all"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_product_and_scatter_give_the_same_sums(monkeypatch, share, routing,
                                                with_live, dtype):
    probs = _probs(share, routing)
    x, wi, wo = _operands(share, dtype)
    live = _live_mask() if with_live else None
    (prod, prod32), (scat, scat32) = _both_forms(
        monkeypatch, lambda: _held(share, probs, x, wi, wo, dtype, live))
    stats = prod[3]
    tokens = int(live.sum()) if with_live else T
    assert float(stats["moe_routed_pairs"]) == tokens * K
    assert float(stats["moe_held_pairs_dropped"]) == 0.0
    held = float(stats["moe_held_pairs"])
    assert held == {"none": 0, "all": tokens * K}.get(routing, held)
    if routing == "mixed":
        assert K < held < tokens * K
    prod32, scat32 = np.asarray(prod32), np.asarray(scat32)
    assert prod32.dtype == scat32.dtype == np.float32
    # The float32 sums of at most K products each: equal to float32
    # rounding of the largest of them.
    np.testing.assert_allclose(
        prod32, scat32, rtol=1e-6,
        atol=1e-6 * max(np.abs(scat32).max(), 1e-30))
    got = np.asarray(prod[0], np.float32)
    want = np.asarray(scat[0], np.float32)
    assert prod[0].dtype == dtype
    if dtype == jnp.bfloat16:
        # the one cast at the end rounds the sums' last places away
        assert np.array_equal(got, want)
    else:
        assert np.array_equal(got, prod32.reshape(got.shape))
    if routing == "none":
        assert not want.any()
    if routing == "mixed":
        assert np.abs(want[0, 0]).max() > 0  # every pick held
        assert not want[0, 1].any()  # none held
        if with_live:
            assert not want[0, 3].any() and not want[0, 30:].any()


def _nan_tail_gmm(lhs, rhs, group_sizes, preferred_element_type, **_):
    """The megablox kernel's contract on the chip: rows past
    sum(group_sizes) are not written (here: NaN)."""
    out = moe._pick_gmm()(lhs, rhs, group_sizes, preferred_element_type)
    kept = jnp.arange(lhs.shape[0])[:, None] < group_sizes.sum()
    return jnp.where(kept, out, jnp.nan)


@pytest.mark.parametrize("routing", ["mixed", "none"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_a_nan_past_the_kept_rows_reaches_no_token(monkeypatch, share,
                                                   routing):
    """A NaN times a zero of P is NaN: the mask stands in front of the
    product as it stood in front of the scatter."""
    probs = _probs(share, routing)
    x, wi, wo = _operands(share, jnp.bfloat16)
    want = _held(share, probs, x, wi, wo, jnp.bfloat16)[0]
    (prod, _), (scat, _) = _both_forms(
        monkeypatch, lambda: _held(share, probs, x, wi, wo, jnp.bfloat16,
                                   gmm_fn=_nan_tail_gmm))
    for got in (prod[0], scat[0]):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        assert np.array_equal(got, np.asarray(want, np.float32))


@pytest.mark.parametrize("with_live", [False, True], ids=["all_rows", "live"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_gradients_agree_across_the_forms(monkeypatch, share, with_live):
    """A product's VJP is two products: x, wi, wo and the router's logits
    get the gradients the scatter's gather gave them."""
    E = SHARES[share][0]
    x, wi, wo = _operands(share, jnp.float32)
    logits = jnp.log(_probs(share, "mixed") + 0.02)
    live = _live_mask() if with_live else None
    mix = jax.random.normal(jax.random.key(9), (1, T, H))

    def loss(x, wi, wo, logits):
        out = _held(share, jax.nn.sigmoid(logits), x, wi, wo, jnp.float32,
                    live)[0]
        return jnp.sum(out * mix)

    (prod, _), (scat, _) = _both_forms(
        monkeypatch,
        lambda: jax.grad(loss, argnums=(0, 1, 2, 3))(x, wi, wo, logits))
    assert prod[3].shape == (1, T, E)
    for got, want in zip(prod, scat):
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all() and np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("tokens,rows,hidden,dtype,product", [
    (288, 2304, 7168, "bfloat16", True),   # kimi-k2-7-code-serve-longctx
    (288, 2304, 4096, "bfloat16", True),   # command-a-plus-serve-mixed
    (16384, 8192, 2304, "bfloat16", False),  # kimi-linear-train-8k
    (16384, 8192, 2304, "float32", False),
    (T, 288, H, "float32", True),          # this file's, tier-1's tiny ones
    (T, 288, H, "bfloat16", True),
], ids=["kimi_k2_tick", "command_a_tick", "kimi_linear_step",
        "kimi_linear_step_fp32", "tiny_fp32", "tiny_bf16"])
def test_the_form_follows_the_shapes(tokens, rows, hidden, dtype, product):
    assert moe.held_combine_is_product(tokens, rows, hidden, dtype) is product
    # by 3 x or more on the estimate, either way: no delicate boundary
    passes = 1 if dtype == "bfloat16" else 6
    mxu = 2.0 * tokens * rows * hidden * passes / 197e12
    scatter = rows * (0.1e-6 + 8.0 * hidden / 400e9)
    assert (scatter >= 3 * mxu) if product else (mxu >= 2.5 * scatter)


def test_the_form_is_decided_by_the_token_rows():
    """R cancels: a row of the sorted buffer costs T multiply-adds an
    element as a product and one slow add as a scatter."""
    for rows in (128, 2304, 1 << 20):
        assert moe.held_combine_is_product(288, rows, 7168, jnp.bfloat16)
        assert not moe.held_combine_is_product(8192, rows, 7168, jnp.bfloat16)


def _served_config(**over):
    kw = dict(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=2, intermediate_size=48, moe_intermediate_size=48,
        seq_length=64, use_moe=True, moe_pattern="all", num_experts=8,
        moe_top_k=2, experts_held=(0, 4), moe_dispatch="gmm",
        capacity_factor=2.0, precision="fp32", use_flash_attention=False,
        use_stable_embedding=False, scan_layers=False, prefill_chunk_size=6,
        routing_noise_std=0.0, attention_backend="ragged_xla",
        max_new_tokens=8,
    )
    kw.update(over)
    cfg = Config(**kw)
    cfg.validate()
    return cfg


class _Tok:
    vocab_size = 64
    eos_token_id = pad_token_id = im_end = 65


def _gauge(registry):
    return {fam.name: fam.children()[0].value
            for fam in registry.families()
            if fam.name == "moe_held_combine_product"}


@pytest.mark.parametrize("held", [True, False], ids=["held", "every_expert"])
def test_the_scheduler_says_which_form_its_tick_has(held):
    from luminaai_tpu.inference.generate import GenerationEngine
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.parallel.sharding import unbox
    from luminaai_tpu.serving.server import ContinuousScheduler

    cfg = _served_config(**({} if held else dict(
        experts_held=None, moe_dispatch="sort", capacity_factor=4.0)))
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = GenerationEngine(model, params, _Tok(), cfg)
    registry, recorder = MetricsRegistry(), FlightRecorder()
    sched = ContinuousScheduler(
        engine, num_slots=2, page_size=4, max_slot_tokens=32,
        registry=registry, recorder=recorder)
    said = [e for e in recorder.snapshot() if e["type"] == "moe_held_combine"]
    if not held:
        assert sched.decoder.held_combine is None
        assert _gauge(registry) == {} and said == []
        return
    # 2 lanes + 6 chunk rows; 2.0 x 8 x 2 x 4 / 8 = 16 -> one row tile
    assert sched.decoder.held_combine == {
        "T": 8, "R": 128, "H": 32, "product": True}
    assert _gauge(registry) == {"moe_held_combine_product": 1}
    assert len(said) == 1 and said[0]["T"] == 8 and said[0]["product"]


def test_the_trainer_says_which_form_its_step_has(tmp_path, monkeypatch):
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.training.trainer import Trainer

    def trainer(**over):
        cfg = _served_config(
            batch_size=2, seq_length=16, max_steps=2,
            gradient_checkpointing=False, output_dir=str(tmp_path),
            eval_every_n_batches=10**6, save_every_n_batches=10**6, **over)
        reg, rec = MetricsRegistry(), FlightRecorder()
        t = Trainer(cfg, train_data=[], registry=reg, recorder=rec,
                    checkpoint_dir=str(tmp_path / "ckpt"))
        return t, reg, rec

    t, reg, rec = trainer()
    try:
        assert _gauge(reg) == {"moe_held_combine_product": 1}
        said = [e for e in rec.snapshot() if e["type"] == "moe_held_combine"]
        assert [(e["T"], e["R"], e["H"], e["product"]) for e in said] == [
            (32, 128, 32, True)]
        # A step rebuilt at shapes on the other side of the rule says so
        # (here: the rule moved, the way a training batch's T moves it).
        monkeypatch.setattr(moe, "held_combine_is_product",
                            lambda *shape: False)
        t._rebuild_steps("test")
        assert _gauge(reg) == {"moe_held_combine_product": 0}
    finally:
        t.close()
    t, reg, rec = trainer(experts_held=None, moe_dispatch="sort",
                          capacity_factor=4.0)
    try:
        assert _gauge(reg) == {}
        assert not [e for e in rec.snapshot()
                    if e["type"] == "moe_held_combine"]
    finally:
        t.close()
