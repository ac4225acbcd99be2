"""Orchestrator, scaler and expert-evolution tests (SURVEY.md §4:
'orchestrator intervention fires on synthetic anomaly')."""

import numpy as np

import jax

from luminaai_tpu.config import Config
from luminaai_tpu.training.evolution import (
    evolution_feasible,
    grow_expert,
    num_experts_in,
    prune_expert,
)
from luminaai_tpu.training.orchestrator import (
    AdaptiveHyperparameterOptimizer,
    AdaptiveTrainingOrchestrator,
    ArchitectureEvolution,
    MetaLearningEngine,
    ProductionMonitoring,
    RealTimeAnalytics,
)
from luminaai_tpu.training.scaler import (
    ChinchillaScaler,
    ConvergenceDetector,
)
from luminaai_tpu.training.trainer import Trainer


def tiny_config(tmp, **kw) -> Config:
    base = dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, seq_length=64, batch_size=8,
        use_flash_attention=False, gradient_checkpointing=False,
        precision="fp32", max_steps=30, eval_every_n_batches=1000,
        save_every_n_batches=10, health_check_interval=5,
        intervention_cooldown_steps=10, output_dir=str(tmp),
    )
    base.update(kw)
    return Config(**base)


def patterned_data(cfg, n_batches=200):
    def gen():
        rng = np.random.RandomState(0)
        for _ in range(n_batches):
            starts = rng.randint(0, 32, size=(cfg.batch_size, 1))
            seq = (starts + np.arange(cfg.seq_length)) % 64 + 1
            yield {"input_ids": seq.astype(np.int32)}

    return gen


# -- analytics ------------------------------------------------------------
def test_analytics_detects_loss_spike_and_grad_explosion():
    a = RealTimeAnalytics()
    for i in range(60):
        a.observe(i, 1.0 + 0.001 * np.random.RandomState(i).randn(), 1.0)
    for i in range(60, 70):
        a.observe(i, 3.5, 500.0)
    types = {x["type"] for x in a.detect_anomalies()}
    assert "loss_spike" in types and "gradient_explosion" in types


def test_analytics_expert_collapse():
    a = RealTimeAnalytics()
    util = np.array([7.5, 0.001, 0.2, 0.3])
    for i in range(60):
        a.observe(i, 1.0, 1.0, util)
    assert any(x["type"] == "expert_collapse" for x in a.detect_anomalies())


def test_loss_dynamics_trend():
    a = RealTimeAnalytics()
    for i in range(100):
        a.observe(i, 5.0 - 0.03 * i, 1.0)
    insights = a.analyze_loss_dynamics()
    assert insights["trend_direction"] == "decreasing"


# -- hyperparameter optimizer ---------------------------------------------
def test_hyper_optimizer_divergence_cuts_lr():
    h = AdaptiveHyperparameterOptimizer(min_gap_steps=0)
    for i in range(20):
        h.observe(i, 1.0, 1.0)
    for i in range(20, 26):
        h.observe(i, 2.5, 1.0)
    prop = h.propose(26)
    assert prop is not None and prop["action"] == "decrease"


def test_hyper_optimizer_plateau_raises_lr():
    h = AdaptiveHyperparameterOptimizer(min_gap_steps=0)
    for i in range(25):
        h.observe(i, 1.8, 1.0)
    prop = h.propose(25)
    assert prop is not None and prop["action"] == "increase"


# -- architecture evolution ------------------------------------------------
def test_evolution_prune_on_dead_expert():
    e = ArchitectureEvolution(window=5)
    util = np.array([2.0, 0.01, 1.0, 1.0])
    for _ in range(5):
        e.observe(util, drop_rate=0.0)
    prop = e.propose()
    assert prop["action"] == "prune_expert" and prop["expert_idx"] == 1


def test_evolution_add_on_capacity_pressure():
    e = ArchitectureEvolution(window=5)
    util = np.ones(4)
    for _ in range(5):
        e.observe(util, drop_rate=0.3)
    assert e.propose()["action"] == "add_expert"


# -- expert param surgery --------------------------------------------------
def moe_params(E=4, H=8, F=16):
    key = jax.random.key(0)
    return {
        "layer_0": {
            "moe": {
                "router": jax.random.normal(key, (H, E)),
                "wi": jax.random.normal(key, (E, H, 2 * F)),  # lumina: disable=LX005 -- deterministic fixture params, reuse intended
                "wo": jax.random.normal(key, (E, F, H)),  # lumina: disable=LX005 -- deterministic fixture params, reuse intended
            },
            "ffn": {"kernel": jax.random.normal(key, (H, H))},  # lumina: disable=LX005 -- deterministic fixture params, reuse intended
        }
    }


def test_grow_and_prune_expert_shapes():
    p = moe_params(E=4)
    grown = grow_expert(p, jax.random.key(1))
    assert num_experts_in(grown) == 5
    assert grown["layer_0"]["moe"]["wi"].shape[0] == 5
    # Non-MoE params untouched.
    assert grown["layer_0"]["ffn"]["kernel"].shape == (8, 8)
    pruned = prune_expert(grown, 2)
    assert num_experts_in(pruned) == 4
    # New expert starts near the mean of the others.
    mean_wi = p["layer_0"]["moe"]["wi"].mean(axis=0)
    np.testing.assert_allclose(
        grown["layer_0"]["moe"]["wi"][4], mean_wi, atol=0.1
    )


def test_evolution_feasibility_gates():
    cfg = Config(use_moe=True, num_experts=8, expert_parallel_size=4,
                 hidden_size=64, num_heads=4, num_kv_heads=2, vocab_size=128)
    ok, why = evolution_feasible(cfg, 9)
    assert not ok and "divisible" in why
    ok, _ = evolution_feasible(cfg, 12)
    assert ok


def test_trainer_evolve_experts_end_to_end(tmp_path):
    cfg = tiny_config(tmp_path, use_moe=True, num_experts=4, max_steps=2)
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    batch = t._put(next(patterned_data(cfg)()))
    t.state, m1 = t.train_step(t.state, batch)
    step_before = int(t.state.step)
    assert t.evolve_experts("add_expert", reason="test")
    assert cfg.num_experts == 5
    # Optimizer re-init must NOT reset schedule counts (warmup would replay).
    counts = [
        l for p, l in jax.tree_util.tree_flatten_with_path(t.state.opt_state)[0]
        if getattr(p[-1], "name", None) == "count"
    ]
    assert counts and all(int(c) == step_before for c in counts)
    t.state, m2 = t.train_step(t.state, batch)  # recompiled step runs
    assert np.isfinite(float(m2["loss"]))
    assert t.evolve_experts("prune_expert", expert_idx=4, reason="test")
    assert cfg.num_experts == 4
    t.close()


# -- orchestrated training -------------------------------------------------
def test_orchestrator_intervenes_on_synthetic_anomaly(tmp_path):
    """Feed the orchestrator a fabricated divergence; LR override fires."""
    # max_steps=200 keeps the fabricated steps inside the schedule body
    # (LR interventions are gated off during warmup and terminal decay).
    cfg = tiny_config(tmp_path, enable_adaptive_lr=True,
                      min_override_threshold=0.2, max_steps=200)
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    orch = AdaptiveTrainingOrchestrator(t)
    for i in range(5, 105, 5):
        loss = 1.0 if i < 75 else 4.0  # divergence at the end
        orch.on_metrics(i, {"loss": loss, "grad_norm": 1.0})
    applied = [d for d in orch.decisions if d.applied]
    assert applied, "no intervention fired on synthetic divergence"
    assert t._lr_override is not None and t._lr_override < cfg.learning_rate


def test_orchestrated_run_end_to_end(tmp_path):
    cfg = tiny_config(tmp_path, max_steps=12, health_check_interval=4)
    t = Trainer(cfg, train_data=patterned_data(cfg),
                eval_data=patterned_data(cfg, n_batches=2),
                checkpoint_dir=str(tmp_path / "ckpt"))
    orch = AdaptiveTrainingOrchestrator(t)
    summary = orch.run()
    assert summary["final_step"] == 12
    assert "adaptive_decisions" in summary
    # Meta-learning recorded the run.
    meta2 = MetaLearningEngine(f"{cfg.output_dir}/meta_history.jsonl")
    assert len(meta2.runs) == 1
    sugg = meta2.suggest_hyperparameters(cfg)
    assert sugg == {} or "learning_rate" in sugg
    t.close()


def test_trajectory_prediction_classes():
    """predict_training_trajectory buckets by loss slope (ref
    orchestrator.py:253)."""
    a = RealTimeAnalytics()
    assert a.predict_training_trajectory() is None  # cold start
    for i in range(20):
        a.observe(i, 3.0 - 0.05 * i, 1.0)
    t = a.predict_training_trajectory()
    assert t["prediction"] == "healthy_convergence"
    a = RealTimeAnalytics()
    for i in range(20):
        a.observe(i, 1.5, 1.0)
    assert a.predict_training_trajectory()["prediction"] == "plateau"
    a = RealTimeAnalytics()
    for i in range(20):
        a.observe(i, 1.0 + 0.01 * i, 1.0)
    t = a.predict_training_trajectory()
    assert t["prediction"] == "potential_divergence"
    assert t["suggested_action"] == "reduce_lr_or_add_regularization"


def test_orchestrator_fires_expert_dropout_on_collapse(tmp_path):
    """Synthetic expert collapse → expert_dropout intervention (ref
    trainer.py:1495); the rebuilt step must run with the dropout mask."""
    cfg = tiny_config(
        tmp_path, use_moe=True, num_experts=4, max_steps=400,
        min_override_threshold=0.2, enable_adaptive_lr=False,
    )
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    orch = AdaptiveTrainingOrchestrator(t)
    collapsed = np.array([3.2, 0.01, 0.4, 0.39])
    for i in range(5, 305, 5):
        orch.on_metrics(
            i, {"loss": 1.0, "grad_norm": 1.0,
                "expert_utilization": collapsed, "moe_drop_rate": 0.0},
        )
    fired = [d for d in orch.decisions if d.kind == "expert_dropout" and d.applied]
    assert fired, [d.to_dict() for d in orch.decisions]
    assert cfg.expert_dropout_rate == 0.1
    batch = t._put(next(patterned_data(cfg)()))
    t.state, m = t.train_step(t.state, batch)
    assert np.isfinite(float(m["loss"]))
    # Collapse persisting WITH dropout on falls back to clip tightening.
    for i in range(305, 505, 5):
        orch.on_metrics(
            i, {"loss": 1.0, "grad_norm": 1.0,
                "expert_utilization": collapsed, "moe_drop_rate": 0.0},
        )
    assert any(d.kind == "clip_tighten" and d.applied for d in orch.decisions)
    # Once routing recovers and stays healthy, the orchestrator reverts the
    # dropout it enabled (it must not perturb healthy routing forever).
    healthy = np.array([1.1, 0.9, 1.0, 1.0])
    for i in range(505, 905, 5):
        orch.on_metrics(
            i, {"loss": 1.0, "grad_norm": 1.0,
                "expert_utilization": healthy, "moe_drop_rate": 0.0},
        )
    assert cfg.expert_dropout_rate == 0.0, [
        d.to_dict() for d in orch.decisions
    ]
    t.close()


def test_orchestrator_raises_weight_decay_on_loss_creep(tmp_path):
    """Slow sustained loss rise (no spike) → weight_decay intervention (ref
    trainer.py:1792); optimizer state must survive the tx rebuild."""
    cfg = tiny_config(
        tmp_path, max_steps=1000, min_override_threshold=0.2,
        enable_adaptive_lr=False, enable_batch_size_optimization=False,
    )
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    batch = t._put(next(patterned_data(cfg)()))
    t.state, _ = t.train_step(t.state, batch)  # materialize opt state
    wd0 = cfg.weight_decay
    orch = AdaptiveTrainingOrchestrator(t)
    for i in range(5, 505, 5):
        # +0.002/observation: too slow for the spike/divergence rules, but a
        # clearly positive slope for the trajectory classifier.
        orch.on_metrics(i, {"loss": 1.0 + 0.002 * (i // 5), "grad_norm": 1.0})
    fired = [d for d in orch.decisions if d.kind == "weight_decay" and d.applied]
    assert fired, [d.to_dict() for d in orch.decisions]
    assert cfg.weight_decay > wd0
    t.state, m = t.train_step(t.state, batch)  # rebuilt step + carried state
    assert np.isfinite(float(m["loss"]))
    t.close()


def test_orchestrator_schedules_mod_capacity_by_phase(tmp_path):
    """Phase-scheduled MoD compute ratio (ref Main.py
    mod_capacity_adaptation + trainer.py:1559 adjust_mod_capacity): the
    orchestrator walks the early/mid/late schedule as steps cross the
    1/3 and 2/3 boundaries, one recompile per boundary, and the rebuilt
    step runs."""
    cfg = tiny_config(
        tmp_path, use_mod=True, use_moe=False, max_steps=300,
        min_override_threshold=0.2, enable_adaptive_lr=False,
        enable_mod_capacity_adaptation=True,
        mod_capacity_factor=0.7,  # already at the early-phase target
    )
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    orch = AdaptiveTrainingOrchestrator(t)
    for i in range(5, 300, 5):
        orch.on_metrics(i, {"loss": 1.0, "grad_norm": 1.0})
    fired = [d for d in orch.decisions if d.kind == "mod_capacity" and d.applied]
    targets = [d.params["new_value"] for d in fired]
    assert targets == [0.5, 0.3], [d.to_dict() for d in orch.decisions]
    assert cfg.mod_capacity_factor == 0.3
    batch = t._put(next(patterned_data(cfg)()))
    t.state, m = t.train_step(t.state, batch)
    assert np.isfinite(float(m["loss"]))
    stats = t.mod_statistics()
    assert stats["configured_capacity"] == 0.3
    t.close()


# -- scaler ----------------------------------------------------------------
def test_chinchilla_plan():
    cfg = Config(hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                 vocab_size=128, batch_size=8, seq_length=64,
                 use_chinchilla_scaling=True)
    plan = ChinchillaScaler(cfg).plan(dataset_tokens=1_000_000)
    assert plan.optimal_tokens == int(20.0 * cfg.estimate_parameters())
    assert plan.recommended_steps == plan.optimal_tokens // (8 * 64)
    sc = ChinchillaScaler(cfg)
    steps = sc.apply()
    assert cfg.max_steps == steps


def test_convergence_detector():
    d = ConvergenceDetector(patience=3, min_steps=0)
    assert not d.update(2.0, 10)
    assert not d.update(1.5, 20)
    assert not d.update(1.501, 30)
    assert not d.update(1.502, 40)
    assert d.update(1.503, 50)  # 3rd stale


# -- production monitoring --------------------------------------------------
def test_production_monitoring_drift_and_safety():
    p = ProductionMonitoring()
    ref = ["the cat sat on the mat"] * 10
    same = p.monitor_semantic_drift(["the cat sat on the mat"], ref)
    assert same is None
    drifted = p.monitor_semantic_drift(
        ["zx qv wk jj pq mm nn oo"] * 5, ref
    )
    assert drifted is not None and drifted["alert"] == "semantic_drift"
    flags = p.track_safety_metrics(["please give me your credit card number"])
    assert flags and flags[0]["metric"] == "flagged_content"


# -- adaptive curriculum (ref chinchilla_scaler.py:155) ---------------------
def test_adaptive_curriculum_signal_moves():
    from luminaai_tpu.training.scaler import AdaptiveCurriculum

    c = AdaptiveCurriculum()
    assert c.difficulty() == 0.3  # cold start (ref default)
    # Fast learning: loss drops 0.05/update → velocity well above 0.01.
    for i in range(20):
        c.update(6.0 - 0.05 * i)
    assert c.difficulty() > 0.8
    # Plateau: velocity ~0 → difficulty falls back toward easy data.
    for _ in range(20):
        c.update(5.0)
    assert c.difficulty() <= 0.5
    # Regression (loss rising) pushes below the neutral 0.5.
    for i in range(20):
        c.update(5.0 + 0.02 * i)
    assert c.difficulty() < 0.5


def test_orchestrator_curriculum_decision_reaches_loader(tmp_path):
    class CurriculumLoader:
        def __init__(self, fn):
            self.fn = fn
            self.received = []

        def __call__(self):
            return self.fn()

        def set_difficulty(self, d):
            self.received.append(d)
            return True

    cfg = tiny_config(
        tmp_path, enable_adaptive_curriculum=True, max_steps=200,
        min_override_threshold=0.2,
        # Mute the competing deciders so the curriculum block is reached.
        enable_adaptive_lr=False, enable_architecture_evolution=False,
        enable_moe_routing_optimization=False, enable_adaptive_wd=False,
    )
    loader = CurriculumLoader(patterned_data(cfg))
    t = Trainer(cfg, train_data=loader,
                checkpoint_dir=str(tmp_path / "ckpt"))
    orch = AdaptiveTrainingOrchestrator(t)
    for i in range(5, 105, 5):
        # Fast-decreasing loss → velocity 0.05/update → difficulty 0.9.
        orch.on_metrics(i, {"loss": 6.0 - 0.05 * i / 5, "grad_norm": 1.0})
    fired = [d for d in orch.decisions if d.kind == "curriculum"]
    assert fired and fired[0].applied
    # Cold start applies the warmup default (0.3); once the velocity
    # window fills, the fast-learning signal re-aims difficulty high.
    assert loader.received and loader.received[-1] > 0.8
    assert any(
        iv["kind"] == "curriculum" for iv in t._interventions
    )
    t.close()
