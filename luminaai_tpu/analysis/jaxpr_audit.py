"""Abstract-eval auditors: recompile surface, sharding coverage, host ops.

Everything here works on ABSTRACT values — `jax.eval_shape` /
`jax.make_jaxpr` over ShapeDtypeStructs — so no parameter buffer
materializes and no step executes on a device (the one concrete
allocation is the stepwise decoder's zero-filled micro KV pool, KBs at
audit_config sizes). That makes the audits cheap enough to run as a
blocking CI step and honest enough to pin in tests: the numbers
describe the traced program, not a lucky run.

Three auditors:

- `enumerate_recompile_surface` traces the train step and the decode
  steps across the config variants the codebase actually forks on
  (scan_layers on/off, gmm vs capacity einsum dispatch, prefill
  prompt-length scenarios — ONE chunked-prefill executable since the
  LaneMeta unification collapsed the bucket ladder — scalar-offset vs
  batched `cache_index` decode) and hashes each variant's jaxpr. The
  distinct-signature count is the number of executables XLA must
  compile to serve those scenarios — the number ROADMAP item 5's
  unified-forward refactor exists to drive down (prefill went first:
  4 -> 3 decode signatures). `train_recompiles_total` counts the
  symptom at runtime; this enumerates the cause ahead of time.

- `audit_sharding_coverage` walks the abstract boxed param tree and
  flags leaves that carry no logical PartitionSpec annotation
  (GSPMD "annotate, don't fork": an unannotated leaf silently
  replicates and gets whatever layout XLA guesses). Same
  flag-and-export contract as monitoring/attribution.donation_audit.

- `detect_host_transfers` scans a traced jaxpr (recursively, through
  pjit/scan/while/cond sub-jaxprs) for callback/transfer primitives —
  the in-jaxpr counterpart of astlint's LX002 source rule.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, Sequence, Tuple

__all__ = [
    "audit_config",
    "enumerate_recompile_surface",
    "audit_sharding_coverage",
    "detect_host_transfers",
    "enumerate_collectives",
    "audit_ep_dispatch",
    "jaxpr_signature",
]


# Primitives whose presence in a hot-path jaxpr means the step talks to
# the host mid-executable. debug_callback covers jax.debug.print.
HOST_TRANSFER_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "infeed",
        "outfeed",
    }
)


def audit_config(**overrides):
    """Micro config for the auditors: every code-path discriminator the
    enumerator forks on (MoE dispatch, scan, GQA heads) is live, every
    size knob is minimal so traces stay fast. Shapes don't matter for
    the variant COUNT — only which paths exist."""
    import dataclasses as _dc

    from luminaai_tpu.config import ConfigPresets

    cfg = ConfigPresets.debug()
    base = dict(
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=2,
        num_kv_heads=1,
        seq_length=64,
        intermediate_size=128,
        batch_size=2,
        micro_batch_size=None,
        gradient_accumulation_steps=1,
        num_experts=4,
        moe_top_k=2,
        data_parallel_size=1,
        use_flash_attention=False,
        routing_noise_std=0.0,
    )
    base.update(overrides)
    cfg = _dc.replace(cfg, **base)
    cfg.normalize_parallelism()
    return cfg


# --------------------------------------------------------------------------
# jaxpr plumbing
# --------------------------------------------------------------------------


def _iter_sub_jaxprs(params: Dict[str, Any]):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for value in params.values():
        stack = [value]
        while stack:
            v = stack.pop()
            if isinstance(v, (ClosedJaxpr, Jaxpr)):
                yield v
            elif isinstance(v, (list, tuple)):
                stack.extend(v)


def detect_host_transfers(closed_jaxpr) -> Dict[str, int]:
    """Count host-transfer primitives in a jaxpr, recursing through
    pjit/scan/while/cond/custom_vjp sub-jaxprs. {} means clean."""
    counts: Dict[str, int] = {}
    stack = [closed_jaxpr]
    seen: set = set()
    while stack:
        j = stack.pop()
        inner = getattr(j, "jaxpr", j)  # ClosedJaxpr -> Jaxpr
        if id(inner) in seen:
            continue
        seen.add(id(inner))
        for eqn in inner.eqns:
            name = eqn.primitive.name
            if name in HOST_TRANSFER_PRIMITIVES:
                counts[name] = counts.get(name, 0) + 1
            stack.extend(_iter_sub_jaxprs(eqn.params))
    return counts


def _aval_str(tree) -> str:
    import jax

    leaves = jax.tree.leaves(
        tree, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype")
    )
    parts = []
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        parts.append(f"{dtype}{list(shape)}")
    return ";".join(parts)


def jaxpr_signature(fn, *args, program: str, variant: str) -> Dict[str, Any]:
    """Trace `fn(*args)` abstractly and fingerprint the executable it
    would compile to: sha256 over the canonical jaxpr text (shapes,
    dtypes AND ops — two variants merge only when XLA would genuinely
    compile the same program), plus the in/out aval signature and the
    host-transfer census from the same single trace."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    text = str(closed)
    return {
        "program": program,
        "variant": variant,
        "signature": hashlib.sha256(text.encode()).hexdigest()[:16],
        "in_avals": _aval_str(closed.in_avals),
        "out_avals": _aval_str(closed.out_avals),
        "jaxpr_eqns": len(closed.jaxpr.eqns),
        "host_transfer_ops": detect_host_transfers(closed),
    }


# --------------------------------------------------------------------------
# comms auditor: collective-op census + dcn-byte accounting
# --------------------------------------------------------------------------

# Explicit collective primitives (shard_map bodies only — GSPMD-inserted
# collectives happen at compile, after the jaxpr, which is exactly why
# the a2a dispatch keeps its exchanges explicit and auditable).
COLLECTIVE_PRIMITIVES = frozenset(
    {
        "all_to_all",
        "ppermute",
        "psum",
        "pmax",
        "pmin",
        "all_gather",
        "reduce_scatter",
    }
)


def _a2a_stage(params: Dict[str, Any]) -> str:
    """Classify an all_to_all eqn's hierarchy tier from its
    axis_index_groups: the dispatch subsystem builds stage-1 (ICI)
    groups as CONTIGUOUS index blocks — dcn groups of ici members —
    and stage-2 (DCN) groups as STRIDED cross-host rails — ici groups
    of dcn members (parallel/expert_dispatch.hierarchical_groups).
    Degenerate tiers keep the honest label: with ici == 1 the stage-1
    groups are singletons (a no-op intra-host hop) and the single
    stage-2 rail is CONTIGUOUS [0..dcn-1] — one group spanning the
    whole axis is the every-byte-crosses-hosts case, not ICI. No
    groups = the flat single-stage exchange."""
    groups = params.get("axis_index_groups")
    if not groups:
        return "flat"
    g0 = list(groups[0])
    if all(len(g) <= 1 for g in groups):
        return "ici"  # singleton groups: ici tier of a dcn==ep factoring
    contiguous = all(b - a == 1 for a, b in zip(g0, g0[1:]))
    if contiguous and len(groups) == 1:
        return "dcn"  # one full-axis rail: dcn tier of an ici==1 factoring
    return "ici" if contiguous else "dcn"


def _payload_bytes(eqn) -> int:
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * dtype.itemsize
    return total


def enumerate_collectives(closed_jaxpr) -> Dict[str, Any]:
    """Census of explicit collective ops in a jaxpr (recursing through
    pjit/scan/while/cond sub-jaxprs, like detect_host_transfers): per-op
    records with primitive, axis names, payload operand bytes, and —
    for all_to_all — the hierarchy stage. The static counterpart of
    profiling the wire: counts are pinned in tests/test_analysis.py the
    way recompile-surface counts are."""
    ops: List[Dict[str, Any]] = []
    stack = [closed_jaxpr]
    seen: set = set()
    while stack:
        j = stack.pop()
        inner = getattr(j, "jaxpr", j)
        if id(inner) in seen:
            continue
        seen.add(id(inner))
        for eqn in inner.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMITIVES:
                params = eqn.params
                axes = params.get("axis_name", params.get("axes"))
                if isinstance(axes, (list, tuple)):
                    axes = tuple(str(a) for a in axes)
                else:
                    axes = (str(axes),)
                rec: Dict[str, Any] = {
                    "primitive": name,
                    "axes": axes,
                    "payload_bytes": _payload_bytes(eqn),
                }
                if name == "all_to_all":
                    rec["stage"] = _a2a_stage(params)
                ops.append(rec)
            stack.extend(_iter_sub_jaxprs(eqn.params))
    counts: Dict[str, int] = {}
    bytes_by: Dict[str, int] = {}
    for rec in ops:
        counts[rec["primitive"]] = counts.get(rec["primitive"], 0) + 1
        bytes_by[rec["primitive"]] = (
            bytes_by.get(rec["primitive"], 0) + rec["payload_bytes"]
        )
    return {"ops": ops, "counts": counts, "bytes_by_primitive": bytes_by}


def audit_ep_dispatch(registry=None) -> Dict[str, Any]:
    """Price the a2a expert-dispatch path against the replicated
    baseline on a simulated dcn×ici CPU mesh — abstractly (make_jaxpr
    over the MoE layer, nothing executes), so bench --smoke can embed
    the comparison without hardware.

    Two programs are traced on the same 8-device ep8 (dcn2 × ici4)
    mesh, flagship routing shape (8 experts top-2, cf 1.25):

      - `a2a`: tokens sharded over (data, fsdp, expert), routed through
        the hierarchical all-to-all. DCN-crossing bytes = the traced
        stage-2 exchange payloads x (dcn-1)/dcn (the off-host block
        fraction of a grouped all-to-all).
      - `replicated_gather` (the gmm path, today's production default):
        tokens replicated over the expert axis, outputs assembled by a
        full-activation psum over 'expert'. DCN-crossing bytes =
        2 x (dcn-1)/dcn x psum payload (hierarchical ring lower bound:
        reduce-scatter + all-gather across hosts).

    The acceptance pin (CI-asserted via extras.ep_dispatch):
    a2a_dcn_bytes strictly below gather_dcn_bytes — the reason the a2a
    path scales expert capacity past one host is precisely that only
    routed tokens cross DCN, ~cf*k/ep of the baseline's full-activation
    payload."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from luminaai_tpu.models.moe import MoELayer
    from luminaai_tpu.parallel.mesh import build_mesh, use_mesh

    n = jax.device_count()
    if n < 4 or n % 2:
        return {
            "available": False,
            "reason": f"needs >= 4 devices for a dcn tier (have {n})",
        }
    ep = min(8, n)
    dcn = 2
    cfg = audit_config(
        batch_size=8,
        num_experts=8,
        moe_top_k=2,
        capacity_factor=1.25,
        moe_dispatch="a2a",
        expert_parallel_size=ep,
        expert_dcn_size=dcn,
        moe_a2a_overlap_chunks=2,
        scan_layers=False,
    )
    x_abs = jax.ShapeDtypeStruct(
        (cfg.batch_size, cfg.seq_length, cfg.hidden_size), jnp.float32
    )

    def trace_layer(layer_cfg):
        layer = MoELayer(layer_cfg, dtype=jnp.float32)
        mesh = build_mesh(layer_cfg, jax.devices()[: ep])
        with use_mesh(mesh):
            pabs = jax.eval_shape(
                layer.init, jax.random.key(0), x_abs
            )
            closed = jax.make_jaxpr(
                lambda p, xx: layer.apply(p, xx)
            )(pabs, x_abs)
        return enumerate_collectives(closed)

    a2a = trace_layer(cfg)
    gather = trace_layer(_dc.replace(cfg, moe_dispatch="gmm"))

    off_host = (dcn - 1) / dcn
    a2a_dcn = sum(
        int(rec["payload_bytes"] * off_host)
        for rec in a2a["ops"]
        if rec["primitive"] == "all_to_all" and rec.get("stage") == "dcn"
    )
    gather_dcn = sum(
        int(2 * rec["payload_bytes"] * off_host)
        for rec in gather["ops"]
        if rec["primitive"] == "psum" and "expert" in rec["axes"]
    )

    from luminaai_tpu.parallel.expert_dispatch import make_dispatch_plan

    # The traced mesh uses exactly `ep` devices with data_parallel_size=1
    # (trace_layer slices jax.devices()[:ep]); the plan must describe
    # THAT program, not the host's full device count — on a >8-device
    # host n//ep would zero out local_groups and desync the embedded
    # plan from the traced census beside it.
    dp = 1
    plan = make_dispatch_plan(
        ep=ep,
        dcn_size=dcn,
        local_groups=cfg.batch_size // (dp * ep),
        seq=cfg.seq_length,
        top_k=cfg.moe_top_k,
        capacity=_moe_capacity(cfg),
        num_experts=cfg.num_experts,
        hidden=cfg.hidden_size,
        itemsize=4,
        overlap_chunks=cfg.moe_a2a_overlap_chunks,
        dp_groups=cfg.batch_size // dp,
    )
    out = {
        "available": True,
        "mesh": {"devices": n, "expert": ep, "dcn": dcn, "ici": ep // dcn},
        "routing": (
            f"{cfg.num_experts} experts top-{cfg.moe_top_k} "
            f"cf {cfg.capacity_factor}, seq {cfg.seq_length}, "
            f"batch {cfg.batch_size}"
        ),
        "plan": plan.to_dict(),
        "a2a": {
            "counts": a2a["counts"],
            "bytes_by_primitive": a2a["bytes_by_primitive"],
            "stages": {
                stage: sum(
                    rec["payload_bytes"]
                    for rec in a2a["ops"]
                    if rec.get("stage") == stage
                )
                for stage in ("flat", "ici", "dcn")
            },
        },
        "replicated_gather": {
            "counts": gather["counts"],
            "bytes_by_primitive": gather["bytes_by_primitive"],
        },
        "a2a_dcn_bytes": a2a_dcn,
        "gather_dcn_bytes": gather_dcn,
        "a2a_below_gather": bool(a2a_dcn < gather_dcn),
        "note": (
            "abstract traces on a simulated dcn2 mesh: a2a dcn bytes = "
            "stage-2 exchange payloads x (dcn-1)/dcn; baseline = the "
            "replicated gmm path's expert-axis psum x 2(dcn-1)/dcn "
            "(hierarchical all-reduce lower bound)"
        ),
    }
    try:
        from luminaai_tpu.monitoring.telemetry import get_registry

        reg = registry or get_registry()
        g = reg.gauge(
                "ep_dispatch_audit_dcn_bytes",
            "DCN-crossing payload bytes per MoE layer step at last "
            "ep-dispatch audit",
            labelnames=("path",),
        )
        g.labels(path="a2a").set(float(a2a_dcn))
        g.labels(path="replicated_gather").set(float(gather_dcn))
    except Exception:  # pragma: no cover
        pass
    return out


def _moe_capacity(cfg) -> int:
    """The capacity MoELayer resolves for one sequence group — kept in
    sync with models/moe.py __call__ (rounded to the fp32 sublane)."""
    c = max(
        1,
        int(
            cfg.capacity_factor * cfg.seq_length * cfg.moe_top_k
            / cfg.num_experts
        ),
    )
    if c >= 8:
        c = ((c + 7) // 8) * 8
    return c


# --------------------------------------------------------------------------
# recompile-surface enumerator
# --------------------------------------------------------------------------


def _train_variants(cfg) -> List[Dict[str, Any]]:
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.mesh import build_mesh
    from luminaai_tpu.parallel.sharding import make_init_fn, state_shardings
    from luminaai_tpu.parallel.train_step import make_train_step
    from luminaai_tpu.training.optimizer import make_optimizer, make_schedule

    out = []
    for scan in (False, True):
        for dispatch in ("einsum", "gmm"):
            vcfg = _dc.replace(
                cfg, scan_layers=scan, moe_dispatch=dispatch
            )
            model = LuminaTransformer(vcfg)
            schedule = make_schedule(vcfg, 100)
            tx = make_optimizer(vcfg, 100, schedule)
            mesh = build_mesh(vcfg, jax.devices()[:1])
            shardings = state_shardings(vcfg, model, tx, mesh)
            abstract_state = jax.eval_shape(
                make_init_fn(vcfg, model, tx), jax.random.key(0)
            )
            step = make_train_step(vcfg, model, shardings, mesh, schedule, tx)
            batch = {
                "input_ids": jax.ShapeDtypeStruct(
                    (vcfg.batch_size, vcfg.seq_length), jnp.int32
                )
            }
            out.append(
                jaxpr_signature(
                    step.jitted,
                    abstract_state,
                    batch,
                    program="train",
                    variant=f"scan={'on' if scan else 'off'}/{dispatch}",
                )
            )
    return out


class _AuditTokenizer:
    """Minimal tokenizer contract for GenerationEngine; never decodes."""

    eos_token_id = 1
    pad_token_id = 0
    im_end = 2

    class backend:
        @staticmethod
        def encode(text):
            return [3]

    @staticmethod
    def decode(tokens):
        return " ".join(str(t) for t in tokens)


_DECODE_PREFILL_SCENARIOS = (32, 64)  # prompt lengths to serve


def _decode_variants(cfg) -> List[Dict[str, Any]]:
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.inference.generate import (
        GREEDY_SAMPLE_KEY,
        GenerationEngine,
    )
    from luminaai_tpu.models.transformer import LuminaTransformer

    model = LuminaTransformer(cfg)
    # Abstract params end to end: the engine only ever threads them
    # through as the first argument of the functions we trace, so
    # ShapeDtypeStructs suffice — no init forward runs. The only real
    # buffers below are the stepwise decoder's zero-filled micro KV
    # pool (KBs at audit_config sizes).
    pabs = jax.eval_shape(
        lambda k: model.init(k, jnp.ones((1, 8), jnp.int32)),
        jax.random.key(0),
    )["params"]
    engine = GenerationEngine(model, pabs, _AuditTokenizer(), cfg)
    out = []

    # Prefill scenarios (serve a 32-token prompt, serve a 64-token
    # prompt): under the bucket ladder each prompt-length bucket was its
    # own executable; chunked prefill (config.prefill_chunk_size) feeds
    # every prompt through ONE fixed-chunk step, so the scenarios now
    # share a signature — the first decode-surface reduction the
    # LaneMeta unification bought (ROADMAP item 5). Each scenario is
    # still enumerated so the variant list keeps describing workloads,
    # not implementation details.
    chunk = engine._prefill_chunk_len()
    if chunk:
        caches = jax.eval_shape(
            lambda: model.init_cache(1, engine.max_context)
        )
        for scenario in _DECODE_PREFILL_SCENARIOS:
            out.append(
                jaxpr_signature(
                    engine._make_chunk_prefill_fn(chunk),
                    pabs,
                    caches,
                    jax.ShapeDtypeStruct((1, chunk), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    program="decode",
                    variant=(
                        f"prefill/prompt={scenario}/chunk={chunk}"
                    ),
                )
            )
    else:  # pragma: no cover - legacy bucket-ladder configs
        for bucket in _DECODE_PREFILL_SCENARIOS:
            out.append(
                jaxpr_signature(
                    engine._make_prefill_fn(bucket),
                    pabs,
                    jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                    program="decode",
                    variant=f"prefill/bucket={bucket}",
                )
            )

    # Scalar-offset decode: the single-sequence while-loop body
    # (cache_index is a scalar start offset).
    gen_key = (8,) + GREEDY_SAMPLE_KEY
    caches = jax.eval_shape(lambda: model.init_cache(1, cfg.seq_length))
    out.append(
        jaxpr_signature(
            engine._make_decode(gen_key),
            pabs,
            jax.random.key(0),
            jax.ShapeDtypeStruct((), jnp.int32),
            caches,
            jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.bool_),
            program="decode",
            variant="decode/scalar_offset",
        )
    )

    # Batched cache_index decode: the continuous-batching TICK over the
    # slot-paged pool (one row a lane plus the rows of one prefill chunk;
    # cache_index is a [slots + chunk] vector). A served prompt's chunks
    # ride this executable: the serving path has no chunk program, so the
    # prefill scenarios above are the single-stream generate()'s.
    decoder = engine.make_stepwise(num_slots=2, page_size=16)
    fn, args = decoder.step_fn_and_args()
    abstract_args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            getattr(x, "shape", ()), getattr(x, "dtype", None)
        ),
        args,
    )
    out.append(
        jaxpr_signature(
            fn,
            *abstract_args,
            program="decode",
            variant="decode/batched_cache_index",
        )
    )
    return out


def enumerate_recompile_surface(
    cfg=None,
    programs: Sequence[str] = ("train", "decode"),
    registry=None,
) -> Dict[str, Any]:
    """Trace every config variant of the train/decode steps and report
    the distinct-executable count per program.

    Returns {"programs": {name: {"variants": [...], "distinct_signatures":
    N}}, "total_variants": V, "total_distinct": D, "host_transfer_ops":
    {...}}. D is the pinned baseline number the ROADMAP-item-5 refactor
    drives down; host_transfer_ops aggregates the callback census across
    every enumerated executable (expected empty)."""
    cfg = cfg or audit_config()
    per_program: Dict[str, Any] = {}
    transfers: Dict[str, int] = {}
    total_variants = 0
    all_signatures: set = set()
    for program in programs:
        if program == "train":
            variants = _train_variants(cfg)
        elif program == "decode":
            variants = _decode_variants(cfg)
        else:
            raise ValueError(f"unknown program {program!r}")
        signatures = {v["signature"] for v in variants}
        all_signatures |= signatures
        total_variants += len(variants)
        for v in variants:
            for prim, n in v["host_transfer_ops"].items():
                transfers[prim] = transfers.get(prim, 0) + n
        per_program[program] = {
            "variants": variants,
            "distinct_signatures": len(signatures),
        }
    out = {
        "programs": per_program,
        "total_variants": total_variants,
        "total_distinct": len(all_signatures),
        "host_transfer_ops": transfers,
        "note": (
            "abstract enumeration (nothing executed): distinct jaxpr "
            "signatures per program = executables XLA must compile to "
            "cover the enumerated scenarios; ROADMAP item 5 drives "
            "this down"
        ),
    }
    _export_surface_gauges(out, registry)
    return out


def _export_surface_gauges(out: Dict[str, Any], registry) -> None:
    from luminaai_tpu.monitoring.telemetry import get_registry

    registry = registry or get_registry()
    g = registry.gauge(
        "analysis_recompile_surface",
        "Distinct abstract step signatures per program at last audit "
        "(static counterpart of train_recompiles_total)",
        labelnames=("program",),
    )
    for program, rec in out["programs"].items():
        g.labels(program=program).set(float(rec["distinct_signatures"]))
    registry.gauge(
        "analysis_host_transfer_ops",
        "Host callback/transfer primitives found inside enumerated hot-"
        "path jaxprs at last audit (expected 0)",
    ).set(float(sum(out["host_transfer_ops"].values())))


# --------------------------------------------------------------------------
# sharding-coverage auditor
# --------------------------------------------------------------------------


def audit_sharding_coverage(
    cfg=None, registry=None
) -> Dict[str, Any]:
    """Walk the abstract boxed param tree and flag leaves with no
    explicit PartitionSpec (nn.Partitioned names). Same contract as
    donation_audit: flags and exports gauges, never raises."""
    import flax.linen as nn

    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.monitoring.telemetry import get_registry
    from luminaai_tpu.parallel.sharding import _abstract_boxed_params

    cfg = cfg or audit_config()
    model = LuminaTransformer(cfg)
    boxed = _abstract_boxed_params(cfg, model)

    annotated = 0
    flagged: List[Dict[str, Any]] = []

    def walk(tree, path: Tuple[str, ...]) -> None:
        nonlocal annotated
        if isinstance(tree, nn.Partitioned):
            annotated += 1
            return
        if isinstance(tree, dict):
            for k in sorted(tree):
                walk(tree[k], path + (str(k),))
            return
        if hasattr(tree, "shape"):
            flagged.append(
                {
                    "path": "/".join(path),
                    "shape": list(getattr(tree, "shape", ())),
                    "dtype": str(getattr(tree, "dtype", "?")),
                }
            )
            return
        items = getattr(tree, "items", None)
        if callable(items):
            for k, v in sorted(items()):
                walk(v, path + (str(k),))

    walk(boxed, ())
    total = annotated + len(flagged)
    out: Dict[str, Any] = {
        "total_leaves": total,
        "annotated_leaves": annotated,
        "unannotated_leaves": len(flagged),
        "coverage": round(annotated / total, 4) if total else None,
        "flagged": flagged[:50],
        "note": (
            "GSPMD 'annotate, don't fork': a param leaf with no logical "
            "PartitionSpec replicates silently and takes whatever "
            "layout XLA guesses"
        ),
    }
    registry = registry or get_registry()
    if out["coverage"] is not None:
        registry.gauge(
            "sharding_annotation_coverage",
            "Fraction of param leaves carrying an explicit logical "
            "PartitionSpec at last audit (1.0 = fully annotated)",
        ).set(out["coverage"])
    registry.gauge(
        "sharding_unannotated_leaves",
        "Param leaves with no explicit PartitionSpec at last audit",
    ).set(float(len(flagged)))
    return out


# --------------------------------------------------------------------------
# combined entry point (what `lumina analyze` calls)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class AuditVerdict:
    """One auditor's pass/fail plus its full report."""

    name: str
    ok: bool
    detail: Dict[str, Any]


def run_audits(
    cfg=None, registry=None, programs: Sequence[str] = ("train", "decode")
) -> Tuple[List[AuditVerdict], Dict[str, Any]]:
    """Run the abstract auditors; the boolean verdicts drive the
    `lumina analyze` exit code, the report dict rides in --json."""
    cfg = cfg or audit_config()
    verdicts: List[AuditVerdict] = []

    try:
        surface = enumerate_recompile_surface(
            cfg, programs=programs, registry=registry
        )
        # The surface count itself is informational (the refactor
        # baseline); host transfers inside the enumerated hot paths
        # are a failure.
        verdicts.append(
            AuditVerdict(
                "host_transfers",
                ok=not surface["host_transfer_ops"],
                detail={"host_transfer_ops": surface["host_transfer_ops"]},
            )
        )
    except Exception as e:  # never wedge the gate on an audit crash...
        surface = {"error": f"{type(e).__name__}: {e}"}
        # ...but a crash is a FAILURE: an unenumerable surface means
        # the audit lost its subject, not that the repo is clean.
        verdicts.append(
            AuditVerdict("host_transfers", ok=False, detail=surface)
        )

    try:
        coverage = audit_sharding_coverage(cfg, registry=registry)
        verdicts.append(
            AuditVerdict(
                "sharding_coverage",
                ok=coverage["unannotated_leaves"] == 0,
                detail={
                    "coverage": coverage["coverage"],
                    "unannotated_leaves": coverage["unannotated_leaves"],
                    "flagged": coverage["flagged"],
                },
            )
        )
    except Exception as e:
        coverage = {"error": f"{type(e).__name__}: {e}"}
        verdicts.append(
            AuditVerdict("sharding_coverage", ok=False, detail=coverage)
        )

    report = {"recompile_surface": surface, "sharding_coverage": coverage}
    return verdicts, report
