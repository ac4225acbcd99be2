"""The yardstick for `correct` is itself checked: this architecture's
reference against LuminaTransformer at a tiny size on the CPU (two periods
of four layers, the attention layer third in each: ssm, ssm, attention,
ssm): the uncached logits, the loss and the gradient of every parameter;
the catalog's row against the configuration file; the work counts. The
CACHED path (the state pool, the tick's kernel) is held to the same
reference in tests/test_ssm_serving.py. The modules are reached as a cell
reaches them, by the architecture's name."""

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, manifest, model_config

JAMBA = manifest.Architecture("jamba")
jamba_reference, jamba_adapter = JAMBA.reference, JAMBA.adapter

JAMBA_TINY = {
    "attn_layer_offset": 2, "attn_layer_period": 4, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 160, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 4,
    "mamba_expand": 2, "mamba_proj_bias": False, "num_attention_heads": 4,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 8,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 512,
    "program": {"precision": "fp32", "use_flash_attention": False,
                "use_stable_embedding": False,
                "gradient_checkpointing": False},
}


def _jamba_build(body, **over):
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    cfg = model_config.build_config(JAMBA, body, seq_length=128,
                                    batch_size=2, **over)
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])

    def stir(path, x):
        # Initialised at 0 or 1, a bias, a norm or the skip would hide a
        # term that is left out or applied twice.
        name = jax.tree_util.keystr(path)
        if any(k in name for k in ("conv_bias", "_norm", "['D']")):
            return x + 0.3 * jax.random.normal(
                jax.random.key(len(name)), x.shape)
        return x

    return cfg, model, jax.tree_util.tree_map_with_path(stir, params)


def _jamba_ids(rows=2, length=100):
    return jnp.asarray(np.random.RandomState(0).randint(
        3, 512, size=(rows, length)), jnp.int32)


def test_jamba_adapter_names_the_layers():
    cfg, _, params = _jamba_build(JAMBA_TINY)
    kinds = ("ssm", "ssm", "attention", "ssm") * 2
    assert cfg.layer_mixers == kinds and not cfg.use_rope
    assert jamba_reference.from_config_file(JAMBA_TINY)["layer_kinds"] == kinds
    assert (cfg.ssm_inner(), cfg.ssm_state_size, cfg.ssm_rank()) == (128, 16, 4)
    assert cfg.keeps_lane_state() and not cfg.recurrent_or_latent()
    ssm = params["layer_0"]["ssm"]
    assert ssm["A_log"].shape == (16, 128) and ssm["w_x"].shape == (128, 36)
    assert params["layer_2"]["attention"]["wk"].shape == (64, 1, 16)
    assert "lm_head" not in params["embedder"]
    # The state decays over tens of tokens at these initialisers, so a
    # comparison over 100 tokens tests a recurrence and not a reset.
    dt = jax.nn.softplus(ssm["dt_bias"])
    per_token = jnp.exp(-dt[None, :] * jnp.exp(ssm["A_log"]))
    assert 0.2 < float(per_token.min()) and float(per_token.max()) < 0.9995
    assert float(jnp.median(per_token)) ** 30 < 0.5


def test_jamba_logits_loss_and_every_gradient_match():
    """The program's uncached forward (the chunked XLA scan from zero
    state, float32) against the reference's token-by-token recurrence and
    `jax.grad`: logits to 1e-4 of their spread, the next-token loss, and
    the gradient of the loss in every parameter, all finite."""
    cfg, model, params = _jamba_build(JAMBA_TINY)
    ids = _jamba_ids()
    kw = jamba_reference.from_config_file(JAMBA_TINY)

    def program_loss(p):
        logits = jamba_adapter.program_logits(model, p, ids)
        return correct.next_token_loss(logits, ids), logits

    def reference_loss(p):
        logits = jamba_reference.forward(
            jamba_adapter.params_view(cfg, p), ids, **kw)
        return correct.next_token_loss(logits, ids), logits

    (loss_p, got), grad_p = jax.jit(
        jax.value_and_grad(program_loss, has_aux=True))(params)
    (loss_r, want), grad_r = jax.jit(
        jax.value_and_grad(reference_loss, has_aux=True))(params)
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert verdict["ok"], verdict
    assert abs(float(loss_p) - float(loss_r)) < 1e-5
    flat_p = jax.tree_util.tree_leaves_with_path(grad_p)
    flat_r = jax.tree.leaves(grad_r)
    assert len(flat_p) == len(flat_r) > 100
    for (path, a), b in zip(flat_p, flat_r):
        name = jax.tree_util.keystr(path)
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max())
        assert scale > 0.0, f"{name}: the reference's gradient is zero"
        assert float(jnp.abs(a - b).max()) <= 2e-4 * scale + 1e-9, name


def test_jamba_reference_walks_the_state_token_by_token():
    """The reference's own recurrence, against the closed form over three
    tokens of one channel and one state: h_3 C_3 by hand."""
    mw = {k: jnp.asarray(v, jnp.float32) for k, v in {
        "w_in": [[1.0, 0.5]], "conv": [[0.0], [1.0]], "conv_bias": [0.0],
        "w_x": [[1.0, 1.0, 1.0]], "dt_norm": [1.0], "b_norm": [1.0],
        "c_norm": [1.0], "w_dt": [[1.0]], "dt_bias": [0.0],
        "A_log": [[0.0]], "D": [0.0], "w_out": [[1.0]]}.items()}
    u = jnp.asarray([[[1.0], [2.0], [3.0]]])
    got = np.asarray(jamba_reference._mamba(u, mw, 0.0))[0, :, 0]
    # a norm over one element is its sign: dt = softplus(1), B = C = 1
    x = np.asarray(jax.nn.silu(u[0, :, 0]))
    dt = float(jax.nn.softplus(1.0))
    h, want = 0.0, []
    for t in range(3):
        h = np.exp(-dt) * h + dt * x[t]
        z = 0.5 * float(u[0, t, 0])
        want.append(h * z / (1.0 + np.exp(-z)))
    assert np.allclose(got, want, rtol=1e-5)


def test_jamba_cell_resolves_this_architecture():
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, "jamba2-3b-serve-burst")
    assert cell.architecture.name == "jamba" and cell.chips == 1
    kw = model_config.config_kwargs(cell.architecture, cell.config)
    kinds = kw["layer_mixers"]
    attn = [i for i, k in enumerate(kinds) if k == "attention"]
    assert attn == [i for i in range(len(kinds)) if i % 14 == 7]
    assert kinds.count("ssm") == len(kinds) - len(attn)
    assert (kw["ssm_state_size"], kw["ssm_dt_rank"], kw["ssm_expand"],
            kw["num_kv_heads"], kw["use_rope"]) == (16, 160, 2, 1, False)
    assert cell.config["deployment"]["prefix_cache_pages"] == 0
    work = cell.architecture.work
    assert set(work.KERNEL_FNS) == manifest.kernel_names("jamba")
    if cell.config["reduced"] == []:
        assert len(kinds) == 28
        assert abs(work.params_total(cell.config) - 3.029e9) < 1e6
    tick = work.ssm_tick(cell.config, {})
    dep = cell.config["deployment"]
    stepped, lanes = dep["lanes_stepped_a_tick"], dep["num_slots"]
    assert 0 < stepped < lanes
    # memory-bound by its counts: the STEPPED lanes' states and the
    # chunk's, read and written, and every row's operands; an idle
    # lane's state is no needed work
    slab = 16 * 5120 * 4
    assert 2 * (stepped + 1) * slab < tick["bytes"] < 2 * lanes * slab
    assert tick["ops"] / 197e12 < tick["bytes"] / 819e9


def test_jamba_file_holds_every_key_of_the_catalog_row():
    """The configuration file against the published config the PR was
    given (the keys this architecture reads, as the catalog has them)."""
    with open(manifest.config_file("jamba2-3b-serve")) as f:
        body = json.load(f)
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "num_attention_heads": 20, "num_experts": 1,
        "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "vocab_size": 65536,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
    }
    for key, value in published.items():
        assert body[key] == value, key
    assert (body["num_hidden_layers"] == 28
            or body["reduced"] == ["num_hidden_layers"])
    assert body["departures"] == []
