"""Chinchilla compute-optimal scaling + convergence detection.

Covers the reference ChinchillaScaler (ref: Src/Main_Scripts/training/
chinchilla_scaler.py — optimal token budget = tokens_per_param × N, epoch/
step derivation from dataset size, convergence detector with patience).
Pure host-side planning: it shapes the step budget the Trainer runs to;
nothing here touches the device.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from luminaai_tpu.config import Config


@dataclass
class ScalingPlan:
    """Resolved training budget (ref chinchilla_scaler.py budget calc)."""

    total_params: int
    active_params: int
    optimal_tokens: int
    tokens_per_step: int
    recommended_steps: int
    recommended_epochs: float
    dataset_tokens: Optional[int] = None

    def to_dict(self) -> Dict[str, Any]:
        return dict(self.__dict__)


class ChinchillaScaler:
    """Compute-optimal budget planning for a config + dataset size."""

    def __init__(self, config: Config):
        self.config = config

    def plan(self, dataset_tokens: Optional[int] = None) -> ScalingPlan:
        cfg = self.config
        total = cfg.estimate_parameters()
        active = cfg.estimate_active_parameters()
        # Chinchilla: ~20 tokens per parameter; for MoE, scale by ACTIVE
        # params (the FLOPs driver), matching ref MoE-aware budgeting.
        basis = active if cfg.use_moe else total
        optimal_tokens = int(cfg.tokens_per_param * basis)
        tokens_per_step = cfg.batch_size * cfg.seq_length
        steps = max(1, optimal_tokens // tokens_per_step)
        epochs = (
            optimal_tokens / dataset_tokens if dataset_tokens else float("nan")
        )
        return ScalingPlan(
            total_params=total,
            active_params=active,
            optimal_tokens=optimal_tokens,
            tokens_per_step=tokens_per_step,
            recommended_steps=steps,
            recommended_epochs=round(epochs, 2) if dataset_tokens else 0.0,
            dataset_tokens=dataset_tokens,
        )

    def apply(self, dataset_tokens: Optional[int] = None) -> int:
        """Set config.max_steps from the plan (ref applies to epochs).
        Returns the step budget."""
        plan = self.plan(dataset_tokens)
        self.config.max_steps = plan.recommended_steps
        return plan.recommended_steps


class AdaptiveCurriculum:
    """Learning-velocity → difficulty signal (ref chinchilla_scaler.py:155
    AdaptiveCurriculumManager).

    Velocity is the recent mean per-update loss reduction. Difficulty in
    [0.2, 0.9] rises while the model is learning fast (it can absorb
    harder data) and falls back toward easy data when progress stalls —
    the reference's exact mapping. Where the reference only REPORTS the
    number, here the orchestrator applies it: PackedDataset's
    length-quantile curriculum admits documents up to the difficulty
    quantile of the length distribution (doc length as the classic
    difficulty proxy), re-taking effect at the next epoch restart.
    """

    def __init__(self, window: int = 50, recent: int = 10):
        self.window = window
        self.recent = recent
        self._velocity: List[float] = []
        self._prev_loss: Optional[float] = None

    def update(self, loss: float) -> None:
        if not math.isfinite(loss):
            return
        if self._prev_loss is not None:
            self._velocity.append(self._prev_loss - loss)
            if len(self._velocity) > self.window:
                self._velocity = self._velocity[-self.window:]
        self._prev_loss = loss

    def difficulty(self) -> float:
        """Recommended difficulty in [0.2, 0.9]; 0.3 until warmed up
        (ref chinchilla_scaler.py:165 get_recommended_difficulty — with
        one fix: the ref's piecewise map jumps 0.7→0.4 as velocity
        crosses 0.01, which would thrash any hysteresis downstream; here
        both branches meet at v=0, so the map is continuous)."""
        if len(self._velocity) < self.recent:
            return 0.3
        v = float(np.mean(self._velocity[-self.recent:]))
        if v >= 0.0:
            return min(0.9, 0.5 + v * 20.0)
        return max(0.2, 0.5 - abs(v) * 10.0)


class ConvergenceDetector:
    """Early-stop signal on flattening eval loss (ref convergence detector).

    Relative-improvement test with patience, plus a minimum-steps guard so
    warmup noise never triggers it.
    """

    def __init__(
        self,
        patience: int = 5,
        min_relative_improvement: float = 1e-3,
        min_steps: int = 100,
    ):
        self.patience = patience
        self.min_rel = min_relative_improvement
        self.min_steps = min_steps
        self.best: Optional[float] = None
        self.stale = 0
        self.history: List[float] = []

    def update(self, eval_loss: float, step: int) -> bool:
        """Returns True when converged (stop recommended)."""
        self.history.append(eval_loss)
        if self.best is None or eval_loss < self.best * (1.0 - self.min_rel):
            self.best = eval_loss
            self.stale = 0
            return False
        if step < self.min_steps:
            # Warmup noise must not bank staleness toward the patience
            # budget — only count once past the minimum-steps guard.
            self.stale = 0
            return False
        self.stale += 1
        return self.stale >= self.patience

