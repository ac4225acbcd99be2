"""Integration tests: trainer loop, checkpoint save/resume, monitoring.

Mirrors ref Src/tests trainer/e2e coverage (SURVEY.md §4): short train on a
tiny model must reduce loss; checkpoint resume must continue bit-exact;
health monitor must flag synthetic anomalies.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.monitoring.logger import MetricsCollector, TrainingHealthMonitor
from luminaai_tpu.training.trainer import Trainer


def tiny_config(tmp, **kw) -> Config:
    base = dict(
        vocab_size=128,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        seq_length=64,
        batch_size=8,
        use_flash_attention=False,
        gradient_checkpointing=False,
        precision="fp32",
        max_steps=30,
        eval_every_n_batches=10,
        save_every_n_batches=10,
        health_check_interval=10,
        output_dir=str(tmp),
        learning_rate=1e-3,
        warmup_ratio=0.1,
    )
    base.update(kw)
    return Config(**base)


def patterned_data(cfg, n_batches=100):
    """Deterministic repeating token pattern — learnable in a few steps."""

    def gen():
        rng = np.random.RandomState(0)
        for _ in range(n_batches):
            starts = rng.randint(0, 32, size=(cfg.batch_size, 1))
            seq = (starts + np.arange(cfg.seq_length)) % 64 + 1
            yield {"input_ids": seq.astype(np.int32)}

    return gen


def test_train_reduces_loss(tmp_path):
    cfg = tiny_config(tmp_path)
    trainer = Trainer(
        cfg,
        train_data=patterned_data(cfg),
        eval_data=patterned_data(cfg, n_batches=2),
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    first_loss = float(trainer.eval_step(
        trainer.state, trainer._put(next(patterned_data(cfg)()))
    )["loss"])
    summary = trainer.train()
    trainer.close()
    assert summary["final_step"] == 30
    final_loss = summary["final_metrics"]["eval_loss"]
    assert final_loss < first_loss * 0.8, (first_loss, final_loss)
    assert summary["health"]["health_score"] > 50


def test_checkpoint_resume_bit_exact(tmp_path):
    cfg = tiny_config(tmp_path, max_steps=10, save_every_n_batches=10,
                      eval_every_n_batches=1000)
    data = patterned_data(cfg)
    t1 = Trainer(cfg, train_data=data, checkpoint_dir=str(tmp_path / "ckpt"))
    t1.train()
    params_before = jax.device_get(t1.state.params)
    t1.close()

    # Fresh trainer, same dirs: auto-resume must restore step and params.
    t2 = Trainer(cfg, train_data=data, checkpoint_dir=str(tmp_path / "ckpt"))
    assert t2.global_step == 10
    params_after = jax.device_get(t2.state.params)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        params_before, params_after,
    )
    t2.close()


def test_rollback_restores_earlier_step(tmp_path):
    cfg = tiny_config(tmp_path, max_steps=10, save_every_n_batches=5,
                      eval_every_n_batches=1000)
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    t.train()
    t.checkpoints.wait()
    assert t.rollback(to_step=5, reason="test")
    assert t.global_step == 5
    t.close()


def test_resume_across_evolution_boundary(tmp_path):
    """Resume after expert grow (VERDICT r4 #10): growing an expert resets
    optimizer moments and makes older checkpoints shape-incompatible —
    restore discovery must land on the post-surgery checkpoint even after
    rotation, a fresh run must resume with the evolved expert count, and
    rollback must never reach behind the surgery fence."""
    cfg = tiny_config(
        tmp_path, max_steps=6, save_every_n_batches=2,
        eval_every_n_batches=1000, health_check_interval=1000,
        use_moe=True, num_experts=4, moe_top_k=2, save_total_limit=2,
        routing_noise_std=0.0,
    )
    data = patterned_data(cfg)
    t1 = Trainer(cfg, train_data=data, checkpoint_dir=str(tmp_path / "ckpt"))
    t1.train()  # saves at steps 2, 4, 6 (limit 2 rotates step 2 out)
    t1.checkpoints.wait()
    assert t1.evolve_experts("add_expert", reason="test")  # saves at 6 again
    t1.checkpoints.wait()
    fence = t1._min_restorable_step
    assert fence == 6
    # Rollback cannot reach behind the surgery fence (those trees have 4
    # experts; restoring one into a 5-expert state would be shape salad).
    assert not t1.rollback(to_step=4, reason="behind fence")
    assert t1.rollback(to_step=6, reason="at fence")
    wi_shape = t1.state.params["layer_0"]["moe"]["wi"].shape
    assert wi_shape[0] == 5
    params_before = jax.device_get(t1.state.params)
    t1.close()

    # Fresh run, evolved config (the resume error message tells users to
    # set num_experts to the evolved count): discovery must pick the
    # post-surgery save — the latest step — and restore bit-exact.
    cfg2 = tiny_config(
        tmp_path, max_steps=8, save_every_n_batches=2,
        eval_every_n_batches=1000, health_check_interval=1000,
        use_moe=True, num_experts=5, moe_top_k=2, save_total_limit=2,
        routing_noise_std=0.0,
    )
    t2 = Trainer(cfg2, train_data=data, checkpoint_dir=str(tmp_path / "ckpt"))
    assert t2.global_step == 6
    assert t2.state.params["layer_0"]["moe"]["wi"].shape[0] == 5
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        params_before, jax.device_get(t2.state.params),
    )
    # And the resumed run can keep training.
    t2.train()
    assert t2.global_step == 8
    t2.close()

    # A stale config (pre-surgery expert count) fails with the actionable
    # num_experts message, not an opaque shape error.
    cfg3 = tiny_config(
        tmp_path, max_steps=8, use_moe=True, num_experts=4, moe_top_k=2,
        routing_noise_std=0.0,
    )
    with pytest.raises(ValueError, match="num_experts"):
        Trainer(cfg3, train_data=data, checkpoint_dir=str(tmp_path / "ckpt"))


def test_lr_override_changes_reported_lr(tmp_path):
    cfg = tiny_config(tmp_path, max_steps=4, eval_every_n_batches=1000,
                      save_every_n_batches=1000, health_check_interval=10)
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    t.adjust_learning_rate(5e-5, reason="test override")
    batch = t._put(next(patterned_data(cfg)()))
    t.state, metrics = t.train_step(t.state, batch)
    assert abs(float(metrics["learning_rate"]) - 5e-5) < 1e-9
    assert t._interventions and t._interventions[0]["kind"] == "lr_override"
    t.close()


# -- monitoring ----------------------------------------------------------
def test_metrics_collector_alerts():
    c = MetricsCollector(loss_spike_threshold=2.0, grad_norm_threshold=10.0)
    for i in range(20):
        c.add_metric("loss", 1.0, i)
    c.add_metric("loss", 5.0, 20)  # spike
    c.add_metric("grad_norm", 50.0, 21)  # above threshold
    c.add_metric("loss", float("nan"), 22)  # critical
    severities = [a.severity for a in c.alerts]
    assert "warning" in severities and "critical" in severities
    assert c.get_health_score() < 80


def test_health_monitor_logs_jsonl(tmp_path):
    m = TrainingHealthMonitor(log_dir=str(tmp_path))
    for i in range(5):
        m.log_step(i, {"loss": 2.0 - 0.1 * i, "grad_norm": 1.0})
    summary = m.get_health_summary()
    assert summary["status"] in ("healthy", "degraded")
    lines = (tmp_path / "metrics.jsonl").read_text().strip().split("\n")
    assert len(lines) == 5
    m.save_health_report(str(tmp_path / "health.json"))
    assert (tmp_path / "health.json").exists()


def test_adam_mu_bf16_trains(tmp_path):
    """adam_mu_dtype='bf16' halves mu HBM; training must still converge and
    the stored first moment must actually be bf16."""
    import dataclasses

    import jax.numpy as jnp
    import optax

    from luminaai_tpu.training.optimizer import make_optimizer

    cfg = dataclasses.replace(tiny_config(tmp_path), adam_mu_dtype="bf16")
    tx = make_optimizer(cfg, 10)
    params = {"w": jnp.zeros((4, 4), jnp.float32)}
    state = tx.init(params)
    found = [
        l.dtype for l in jax.tree.leaves(state)
        if hasattr(l, "dtype") and l.dtype == jnp.bfloat16
    ]
    assert found, "no bf16 leaves in opt state"
    grads = {"w": jnp.ones((4, 4), jnp.float32)}
    updates, state = tx.update(grads, state, params)
    params = optax.apply_updates(params, updates)
    assert all(jnp.isfinite(l).all() for l in jax.tree.leaves(params))


def test_adam_int8_state_loss_parity(tmp_path):
    """adam_state_quantization='int8' (ref trainer.py:771
    create_quantized_optimizer): moments live as int8 codes + row scales.
    The loss trajectory must track fp32 moments closely on a real model,
    and the persistent state must actually be int8."""

    losses = {}
    for name, kw in (
        ("fp32", {}),
        ("int8", {"adam_state_quantization": "int8"}),
    ):
        cfg = tiny_config(tmp_path / name, **kw)
        t = Trainer(cfg, train_data=patterned_data(cfg),
                    checkpoint_dir=str(tmp_path / name / "ckpt"))
        batch = t._put(next(patterned_data(cfg)()))
        run = []
        for _ in range(40):
            t.state, m = t.train_step(t.state, batch)
            run.append(float(m["loss"]))
        losses[name] = run
        if name == "int8":
            n_int8 = sum(
                1 for l in jax.tree.leaves(t.state.opt_state)
                if hasattr(l, "dtype") and l.dtype == jnp.int8
            )
            assert n_int8 > 0, "no int8 leaves in opt state"
        t.close()
    # Both must learn, and the quantized trajectory must stay close.
    assert losses["int8"][-1] < 0.75 * losses["int8"][0], losses["int8"]
    assert abs(losses["int8"][-1] - losses["fp32"][-1]) < max(
        0.25, 0.15 * losses["fp32"][-1]
    ), (losses["fp32"][-1], losses["int8"][-1])
