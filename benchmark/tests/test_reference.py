"""The yardstick for `correct` is itself checked: reference.forward against
LuminaTransformer at a tiny size on the CPU, dense and sparse, uncached
and through StepwiseDecoder's paged cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, model_config, program_adapter, reference

DENSE = {
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 1e6, "tie_word_embeddings": False,
    "program": {"precision": "fp32", "use_flash_attention": False,
                "use_stable_embedding": False, "use_moe": False,
                "gradient_checkpointing": False},
}
SPARSE = dict(
    DENSE, intermediate_size=32, num_key_value_heads=4, num_experts=8,
    num_experts_per_tok=2, rope_theta=1e4, norm_topk_prob=False,
    reference={"moe_combine": "renormalised"},
    program={"precision": "fp32", "use_flash_attention": False,
             "use_stable_embedding": False, "use_moe": True,
             "moe_pattern": "all", "capacity_factor": 4.0,
             "gradient_checkpointing": False, "prefill_chunk_size": 16,
             "attention_backend": "ragged_xla", "max_new_tokens": 8},
)


def _build(body, **over):
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    cfg = model_config.build_config(body, seq_length=64, batch_size=2,
                                    **over)
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])
    return cfg, model, params


@pytest.mark.parametrize("body,scan", [(DENSE, False), (DENSE, True),
                                       (SPARSE, False)],
                         ids=["dense", "dense_scanned", "sparse"])
def test_uncached_forward_matches(body, scan):
    cfg, model, params = _build(body, scan_layers=scan)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(3, 512, size=(2, 48)), jnp.int32)
    got = jax.jit(lambda p, x: program_adapter.program_logits(model, p, x))(
        params, ids)
    want = reference.forward(program_adapter.params_view(cfg, params), ids,
                             **reference.from_config_file(body))
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert verdict["ok"], verdict
    assert abs(float(reference.next_token_loss(got, ids))
               - float(reference.next_token_loss(want, ids))) < 1e-4


def test_as_is_combine_differs_from_renormalised():
    cfg, model, params = _build(SPARSE)
    ids = jnp.arange(3, 35, dtype=jnp.int32)[None]
    view = program_adapter.params_view(cfg, params)
    kw = reference.from_config_file(SPARSE)
    a = reference.forward(view, ids, **kw)
    b = reference.forward(view, ids, **dict(kw, combine="as_is"))
    assert float(jnp.abs(a - b).max()) > 1e-4


@pytest.mark.parametrize("body", [DENSE, SPARSE], ids=["dense", "sparse"])
def test_paged_decode_agrees_with_reference(body):
    """Chunked prefill + decode steps through the scheduler's paged pool
    give the tokens the reference's logits rank first."""
    from luminaai_tpu.inference.generate import GenerationEngine
    from luminaai_tpu.serving.server import ContinuousScheduler

    from benchmark.serve_cell import StubTokenizer

    cfg, model, params = _build(body)
    engine = GenerationEngine(model, params, StubTokenizer(cfg.vocab_size),
                              cfg)
    sched = ContinuousScheduler(engine, num_slots=2, page_size=16,
                                max_slot_tokens=64)
    prompt = np.random.RandomState(1).randint(3, 512, size=40).tolist()
    view = program_adapter.params_view(cfg, params)
    verdict = correct.check_paged_decode(
        sched, prompt, n_new=6,
        ref_logits_fn=lambda ids: reference.forward(
            view, ids, **reference.from_config_file(body)),
        regret_tol=1e-3,
    )
    assert verdict["ok"], verdict
    assert verdict["tokens"] == 6
