"""The comparisons that decide `correct`.

Tolerances, and why. The program computes in bfloat16 (8 bits of
mantissa: 2**-9 relative rounding per operation) with float32
accumulation; the reference in float32 at "highest" matmul precision.
Over a whole forward pass (activations and the residual stream are
stored in bfloat16 between operations) that rounding accumulates to
1.7% of the logits' own spread on the chip at these depths (PERF.md,
PR 24). REL_RMS_TOL is twice that and no more: fp8 (3 bits of mantissa,
2**-4 a rounding, 32 times bf16's) lands far above it, and int8 weights
and activations (about 1% an element in every matmul, on top of the
bf16 storage) above it too.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

REL_RMS_TOL = 0.035  # rms(program - reference) / rms(reference - mean)
# A greedy token may differ from the reference's where two logits tie to
# within rounding: the reference's own preference for its token over the
# program's must then be within this share of the logits' spread.
REGRET_TOL = 0.05


def compare_logits(got, want, rel_rms_tol: float = REL_RMS_TOL
                   ) -> Dict[str, Any]:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    spread = float(np.std(want))
    err = float(np.sqrt(np.mean(np.square(got - want))))
    finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
    rel = err / max(spread, 1e-30)
    return {
        "ok": finite and rel <= rel_rms_tol,
        "rel_rms": rel,
        "max_abs": float(np.abs(got - want).max()),
        "ref_spread": spread,
        "tol": rel_rms_tol,
        "argmax_agree": float(
            np.mean(got.argmax(-1) == want.argmax(-1))
        ),
    }


def reference_continuation(
    ref_logits_fn: Callable[[Any], Any], prompt: Sequence[int], n_new: int
) -> Tuple[List[int], np.ndarray, np.ndarray]:
    """Greedy continuation of `prompt` by the reference's full forward
    pass, one fixed shape: the sequence is padded to its final length and
    causality keeps the padding out of every row that is read. Returns
    (tokens, the n_new logit rows that chose them, the final ids)."""
    L = len(prompt)
    ids = np.zeros((1, L + n_new), np.int32)
    ids[0, :L] = prompt
    rows = []
    tokens: List[int] = []
    for i in range(n_new):
        row = np.asarray(ref_logits_fn(ids)[0, L - 1 + i], np.float32)
        rows.append(row)
        tokens.append(int(row.argmax()))
        ids[0, L + i] = tokens[-1]
    return tokens, np.stack(rows), ids


def check_tokens(got: Sequence[int], want: Sequence[int], rows: np.ndarray,
                 regret_tol: float = REGRET_TOL) -> Dict[str, Any]:
    """Tokens decoded through the paged cache against the reference's
    continuation. Equal tokens pass; at the first that differs the
    reference's logits must hold the two within regret_tol of their
    spread (a rounding tie), and the comparison ends there, because what
    follows was conditioned on another prefix."""
    out: Dict[str, Any] = {"ok": len(got) == len(want), "tokens": len(got),
                           "compared": 0, "tie_at": None, "regret": 0.0,
                           "tol": regret_tol}
    for i, (g, w) in enumerate(zip(got, want)):
        out["compared"] = i + 1
        if g == w:
            continue
        spread = float(np.std(rows[i]))
        regret = float(rows[i][w] - rows[i][g]) / max(spread, 1e-30)
        out.update(tie_at=i, regret=regret)
        out["ok"] = out["ok"] and regret <= regret_tol
        break
    return out


def decode_through_scheduler(sched, prompt: Sequence[int],
                             n_new: int) -> List[int]:
    """One greedy request through `ContinuousScheduler.submit_stream`."""
    tokens = []
    for item in sched.submit_stream(list(prompt), greedy_kwargs(n_new)):
        if isinstance(item, dict):
            break
        tokens.append(int(item))
    return tokens


def greedy_kwargs(max_new: int) -> Dict[str, Any]:
    return {"max_new_tokens": int(max_new), "temperature": 0.0,
            "top_k": 0, "top_p": 1.0, "repetition_penalty": 1.0}


def check_paged_decode(sched, prompt, n_new, ref_logits_fn,
                       regret_tol: float = REGRET_TOL) -> Dict[str, Any]:
    want, rows, _ = reference_continuation(ref_logits_fn, prompt, n_new)
    got = decode_through_scheduler(sched, prompt, n_new)
    return check_tokens(got, want, rows, regret_tol)
