"""The three rehearsals that cost no chip time, for every cell:

    python3 -m benchmark.rehearse                  # all stages, all cells
    python3 -m benchmark.rehearse --stage compile --workload <cell>

  tests    benchmark/tests on the CPU
  tiny     the cell's driver end to end on the CPU at toy widths (four
           virtual devices for a four-chip cell): paths, arguments, control
           flow, the mesh and sharding rules. Prints a rehearsal summary,
           never a result line: a CPU number is not a device metric.
  compile  the cell's real-size programs compiled for a DESCRIBED v5e
           (`v5e:2x2`): XLA's memory_analysis, the Pallas kernel census
           and the collectives found. What the chip's compiler would
           refuse, it refuses here.

Each stage of each cell runs in a child process (jax reads its platform
and device count once). The parent never touches jax.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

TINY = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512}
TINY_MOE = {"num_experts": 8, "num_experts_per_tok": 2,
            "num_key_value_heads": 4, "intermediate_size": 32}


def tiny_cell(cell):
    """The same cell at toy widths (rehearsal only)."""
    cell = copy.copy(cell)
    body = copy.deepcopy(cell.config)
    body.update(TINY)
    if "num_experts" in body:
        body.update(TINY_MOE)
        body["program"]["capacity_factor"] = 4.0
    body["program"]["precision"] = "fp32"
    mix = copy.deepcopy(cell.traffic)
    if mix["kind"] == "train":
        mix.update(seq_length=128, sequences_per_chip=2)
    else:
        body["program"].update(seq_length=256, prefill_chunk_size=16)
        body["deployment"].update(num_slots=4, page_size=16,
                                  max_slot_tokens=256)
        mix["prompt_tokens"] = {"dist": "uniform", "min": 8, "max": 40}
        mix["output_tokens"] = {"dist": "uniform", "min": 4, "max": 12}
        mix.update(preroll_s=1, drain_s=60, trace_seconds=1)
        if mix["kind"] == "open_loop":
            mix["arrivals"]["rate_per_s"] = 6.0
    cell.config, cell.traffic = body, mix
    return cell


def stage_tiny(cell) -> dict:
    import jax

    from benchmark import common, serve_cell, train_cell

    common.setup_compile_cache()
    if cell.chips != len(jax.devices()):
        raise SystemExit(f"rehearsal wants {cell.chips} virtual devices, "
                         f"jax has {len(jax.devices())}")
    train_cell.SAMPLE_TOKENS = 64
    serve_cell.SAMPLE_PROMPT_TOKENS = 40
    device = {"platform": jax.devices()[0].platform, "kind": "rehearsal",
              "count": len(jax.devices()),
              "peak": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    args = SimpleNamespace(seed=2**31 + 12345, seconds=3.0, trace=0,
                           keep_trace=None)
    cell = tiny_cell(cell)
    if cell.traffic["kind"] == "train":
        # The default log cadence (a sync every 10 steps) stays.
        res = train_cell.run(cell, args, device)
    else:
        res = serve_cell.run(cell, args, device)
    return {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metric_names": sorted(res["metrics"])}


def _census(text: str) -> dict:
    from luminaai_tpu.monitoring.attribution import kernel_census

    ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")
    return {
        "kernels": kernel_census(text),
        "collectives": {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                        for op in ops},
    }


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = lambda x: round(x / 1e9, 3)  # noqa: E731
    return {"argument_gb": gb(m.argument_size_in_bytes),
            "output_gb": gb(m.output_size_in_bytes),
            "temp_gb": gb(m.temp_size_in_bytes),
            "alias_gb": gb(m.alias_size_in_bytes),
            "code_gb": gb(m.generated_code_size_in_bytes),
            "peak_gb": gb(m.argument_size_in_bytes + m.output_size_in_bytes
                          + m.temp_size_in_bytes - m.alias_size_in_bytes)}


def stage_compile(cell) -> dict:
    """Real widths, depth and shapes, for a described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from luminaai_tpu.models import moe
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.ops import flash_attention as fa
    from luminaai_tpu.ops import ragged_paged_attention as rpa

    from benchmark import model_config

    jax.config.update("jax_enable_compilation_cache", False)
    fa._interpret = lambda: False   # the code asks default_backend(): cpu here
    rpa._interpret = lambda: False
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    moe._GMM_OVERRIDE = gmm
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[: cell.chips]
    mix = cell.traffic
    out = {"cell": cell.name, "devices": len(devices)}
    t0 = time.time()
    if mix["kind"] == "train":
        from luminaai_tpu.parallel.mesh import build_mesh
        from luminaai_tpu.parallel.sharding import (batch_spec, make_init_fn,
                                                    state_shardings)
        from luminaai_tpu.parallel.train_step import make_train_step
        from luminaai_tpu.training.optimizer import (make_optimizer,
                                                     make_schedule)

        cfg = model_config.build_config(
            cell.config, seq_length=int(mix["seq_length"]),
            batch_size=int(mix["sequences_per_chip"]) * cell.chips)
        mesh = build_mesh(cfg, devices=devices)
        model = LuminaTransformer(cfg)
        schedule = make_schedule(cfg, 10_000)
        tx = make_optimizer(cfg, 10_000, schedule)
        shardings = state_shardings(cfg, model, tx, mesh)
        shapes = jax.eval_shape(make_init_fn(cfg, model, tx),
                                jax.random.key(0))
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)
        batch = {"input_ids": jax.ShapeDtypeStruct(
            (cfg.batch_size, cfg.seq_length), jnp.int32,
            sharding=NamedSharding(mesh, batch_spec()))}
        step = make_train_step(cfg, model, shardings, mesh, schedule, tx)
        with mesh:
            compiled = step.jitted.lower(state, batch).compile()
        out["train_step"] = dict(_memory(compiled),
                                 **_census(compiled.as_text()))
        out["mesh"] = {a: int(n) for a, n in mesh.shape.items()}
    else:
        from luminaai_tpu.inference.generate import (GREEDY_SAMPLE_KEY,
                                                     GenerationEngine)

        from benchmark.serve_cell import StubTokenizer, make_serving_params

        one = SingleDeviceSharding(devices[0])
        cfg = model_config.build_config(cell.config)
        dep = cell.config["deployment"]
        model = LuminaTransformer(cfg)
        pshape = jax.eval_shape(lambda: make_serving_params(model, 0))
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            pshape)
        out["weights_gb"] = round(sum(
            s.size * s.dtype.itemsize for s in jax.tree.leaves(pshape)) / 1e9, 3)
        # The decoder builds its pool with jnp.zeros on the default (CPU)
        # backend; only shapes are taken from it.
        engine = GenerationEngine(model, pshape, StubTokenizer(cfg.vocab_size),
                                  cfg)
        dec = engine.make_stepwise(
            num_slots=int(dep["num_slots"]), page_size=int(dep["page_size"]),
            max_slot_tokens=int(dep["max_slot_tokens"]))
        absd = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
        out["kv_pool_gb"] = round(sum(
            a.size * a.dtype.itemsize
            for a in jax.tree.leaves(dec.pool.caches)) / 1e9, 3)
        dec._active[:] = True
        dec._pos[:] = int(dep["max_slot_tokens"]) - 2
        fn, args = dec.step_fn_and_args(GREEDY_SAMPLE_KEY)
        compiled = fn.lower(params, *absd(args[1:])).compile()
        out["decode_step_widest_extent"] = dict(
            _memory(compiled), **_census(compiled.as_text()))
        chunk = dec._get_chunk_prefill()
        ids = jax.ShapeDtypeStruct((1, dec.prefill_chunk), jnp.int32,
                                   sharding=one)
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        compiled = chunk.lower(params, absd(dec.pool.caches), ids, scalar,
                               scalar, scalar).compile()
        out["prefill_chunk"] = dict(_memory(compiled),
                                    **_census(compiled.as_text()))
        init = jax.jit(lambda: make_serving_params(model, 0),
                       out_shardings=jax.tree.map(lambda _: one, pshape))
        out["weights_init"] = _memory(init.lower().compile())
    out["compile_seconds"] = round(time.time() - t0, 1)
    return out


def child(stage: str, workload: str) -> int:
    from benchmark import manifest

    bench = manifest.load_benchmark()
    faults = manifest.check(bench)
    if faults:
        print("manifest:", faults)
        return 1
    cell = manifest.Cell(bench, workload)
    res = {"tiny": stage_tiny, "compile": stage_compile}[stage](cell)
    print(f"[rehearsal:{stage}:{workload}] " + json.dumps(res, default=str),
          flush=True)
    return 0 if res.get("correct", True) else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--stage", choices=("tests", "tiny", "compile"))
    ap.add_argument("--workload")
    ap.add_argument("--child", action="store_true")
    a = ap.parse_args()
    if a.child:
        return child(a.stage, a.workload)
    from benchmark import manifest

    bench = manifest.load_benchmark()
    cells = [w for w in bench["workloads"]
             if a.workload in (None, w["name"])]
    rc = 0
    stages = [a.stage] if a.stage else ["tests", "tiny", "compile"]
    for stage in stages:
        if stage == "tests":
            cmd = [sys.executable, "-m", "pytest", "benchmark/tests", "-q",
                   "-p", "no:cacheprovider"]
            rc |= subprocess.call(cmd, env=dict(os.environ, JAX_PLATFORMS="cpu"))
            continue
        for w in cells:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            if stage == "tiny":
                env["XLA_FLAGS"] = (
                    f"--xla_force_host_platform_device_count={w['chips']}")
            cmd = [sys.executable, "-m", "benchmark.rehearse", "--child",
                   "--stage", stage, "--workload", w["name"]]
            rc |= subprocess.call(cmd, env=env)
    return rc


if __name__ == "__main__":
    sys.exit(main())
