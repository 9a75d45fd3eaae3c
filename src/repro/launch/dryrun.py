"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

``main`` asks for 512 host-platform placeholder devices (to build the
production meshes) before anything initializes jax, which locks the device
count at first init. Importing this module changes no process state: tests
import its parsers and keep the device count they started with.

Per combination this produces up to three artifacts:

  memory-mode  — full stage count, scanned layers, chunked attention/SSM:
                 the deployable program. compile() proves the sharding is
                 coherent; memory_analysis() proves it fits.
  cost-mode x2 — 1-stage and 2-stage variants with *unrolled* layers and
                 chunk = seq_len (every internal scan has trip count 1), so
                 HloCostAnalysis counts FLOPs/bytes/collectives exactly.
                 Roofline extrapolates: total = cost(1) + (S-1) * delta.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--memory-only]
Outputs JSON under experiments/dryrun/.
"""
import argparse
import dataclasses
import json
import pathlib
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch import mesh as mesh_lib
from repro.launch import sharding, specs
from repro.models import model
from repro.models.config import INPUT_SHAPES, ArchConfig, InputShape, shape_applicable
from repro.optim import adamw

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# memory-mode chunking (bounds the quadratic/recurrent working set per device)
MEM_CHUNK = {"full": 1024, "swa": 1024, "full_bidir": 1024,
             "mamba": 1024, "rwkv": 128}


def _mem_chunk(cfg: ArchConfig) -> int:
    kinds = {s.attn for s in cfg.stage_pattern + cfg.tail_pattern}
    return min(MEM_CHUNK[k] for k in kinds if k in MEM_CHUNK)


# --- collective parsing -------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\])\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op, by kind."""
    out: dict[str, int] = {}
    for shp, kind in _COLL_RE.findall(hlo_text):
        out[kind] = out.get(kind, 0) + _shape_bytes(shp)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


# --- step builders --------------------------------------------------------------

def build_lowered(cfg: ArchConfig, shape: InputShape, mesh, *,
                  mode: str, rules: sharding.ShardingRules | None = None,
                  tp_constraints: bool = False, zero1: bool = False,
                  fsdp_gather: bool = False, stack_fsdp: bool = False):
    """Lower one (arch, shape, mesh, mode) combination. Returns `lowered`."""
    from repro.models import attention, blocks
    rules = rules or sharding.DEFAULT_RULES
    opt_rules = rules
    if zero1:
        # bf16 compute weights: model-sharded only; optimizer state keeps the
        # data-axis FSDP (elementwise update, no matmul -> no gathers)
        rules, opt_rules = sharding.ZERO1_PARAM_RULES, sharding.DEFAULT_RULES
    if stack_fsdp:
        # bf16 compute weights: stack-sharded over data (gathered per stage
        # by the layer scan); optimizer state keeps plain embed->data FSDP —
        # its update is elementwise, which never provokes activation gathers,
        # and it stays sharded even when num_stages % data != 0 (gemma3).
        rules, opt_rules = sharding.STACK_FSDP_RULES, sharding.DEFAULT_RULES

    stage_constraint = None
    if fsdp_gather:
        # Storage stays FSDP (data x model); inside the scan body, re-shard
        # the stage's weights to the model-only compute layout => XLA emits
        # per-stage weight-sized all-gathers (fwd+bwd) and reduce-scatters
        # the weight grads — never activation-sized collectives.
        stage_axes = tuple(blocks.axes_layer(cfg, s) for s in cfg.stage_pattern)
        gather_rules = sharding.ZERO1_PARAM_RULES

        def stage_constraint(stage_params):
            return jax.tree.map(
                lambda spec, leaf: jax.lax.with_sharding_constraint(
                    leaf, gather_rules.named(spec, leaf.shape, mesh)),
                stage_axes, stage_params,
                is_leaf=lambda v: isinstance(v, P))
    cost = mode == "cost"
    chunk = None if cost else _mem_chunk(cfg)
    unroll = cost

    if tp_constraints:
        S = shape.seq_len
        q_shape = (shape.global_batch, S, cfg.num_heads, cfg.head_dim)
        s_shape = (shape.global_batch, cfg.num_heads, S, S)
        attention.set_tp_constraints({
            "qkv": rules.named(P("batch", "seq", "heads_act", "head_dim_act"),
                               q_shape, mesh),
            "scores": rules.named(P("batch", "heads_act", None, None),
                                  s_shape, mesh),
        })
    else:
        attention.set_tp_constraints(None)

    p_specs = specs.params_specs(cfg)
    p_axes = model.param_axes(cfg)
    p_sh = rules.tree_shardings(p_axes, p_specs, mesh)
    b_specs = specs.batch_specs(cfg, shape)
    b_axes = {k: sharding.BATCH_AXES[cfg.input_mode][k] for k in b_specs}
    b_sh = rules.tree_shardings(b_axes, b_specs, mesh)

    if shape.kind == "train":
        o_specs = specs.opt_specs(cfg)
        o_sh = {k: opt_rules.tree_shardings(p_axes, o_specs[k], mesh)
                for k in ("master", "m", "v")}
        o_sh["count"] = NamedSharding(mesh, P())
        step = model.make_train_step(cfg, adamw.AdamWConfig(),
                                     chunk_size=chunk, remat=not cost,
                                     scan_unroll=unroll,
                                     stage_constraint=stage_constraint)
        fn = jax.jit(step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(NamedSharding(mesh, P()), p_sh, o_sh),
                     donate_argnums=(0, 1))
        return fn.lower(p_specs, o_specs, b_specs)

    if shape.kind == "prefill":
        if cfg.encoder_only:
            def step(params, batch):
                return model.encode_step(params, batch, cfg, chunk_size=chunk,
                                         scan_unroll=unroll)
            logits_sh = rules.named(P("batch", "seq", "vocab"),
                                    (shape.global_batch, shape.seq_len,
                                     cfg.vocab_size), mesh)
            fn = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=logits_sh)
            return fn.lower(p_specs, b_specs)

        def step(params, batch):
            return model.prefill_step(params, batch, cfg, chunk_size=chunk,
                                      scan_unroll=unroll)
        c_specs = jax.eval_shape(step, p_specs, b_specs)[1]
        c_axes = model.cache_axes(cfg)
        c_sh = rules.tree_shardings(c_axes, c_specs, mesh)
        logits_sh = rules.named(P("batch", "seq", "vocab"),
                                (shape.global_batch, 1, cfg.vocab_size), mesh)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh),
                     out_shardings=(logits_sh, c_sh))
        return fn.lower(p_specs, b_specs)

    # decode
    c_specs = specs.cache_specs(cfg, shape)
    c_axes = model.cache_axes(cfg)
    c_sh = rules.tree_shardings(c_axes, c_specs, mesh)
    logits_sh = rules.named(P("batch", "seq", "vocab"),
                            (shape.global_batch, 1, cfg.vocab_size), mesh)

    def step(params, cache, batch):
        return model.decode_step(params, cache, batch, cfg, scan_unroll=unroll)

    fn = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                 out_shardings=(logits_sh, c_sh), donate_argnums=(1,))
    return fn.lower(p_specs, c_specs, b_specs)


def _cost_cfg(cfg: ArchConfig, num_stages: int) -> ArchConfig:
    return dataclasses.replace(cfg, num_stages=num_stages)


# --- per-combination driver ------------------------------------------------------

def run_combo(arch: str, shape_name: str, *, multi_pod: bool,
              memory_only: bool = False,
              rules: sharding.ShardingRules | None = None,
              tag: str = "") -> dict:
    cfg = configs.get(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2" if multi_pod else "pod1"
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "kind": shape.kind}

    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        record["skipped"] = reason
        return record

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with mesh:
        lowered = build_lowered(cfg, shape, mesh, mode="memory", rules=rules)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        record["memory"] = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
        }
        record["compile_s"] = round(time.time() - t0, 1)

        if not memory_only:
            # Cost extrapolation anchors: 2- and 4-stage unrolled programs.
            # (1-stage programs let the partitioner make one-off layout
            # choices that poison the delta; 2->4 is stable.)
            for n in (2, 4):
                lo = build_lowered(_cost_cfg(cfg, n), shape, mesh,
                                   mode="cost", rules=rules)
                co = lo.compile()
                ca = co.cost_analysis() or {}
                record[f"cost_{n}stage"] = {
                    "flops": float(ca.get("flops", 0.0)),
                    "bytes": float(ca.get("bytes accessed", 0.0)),
                    "collectives": parse_collectives(co.as_text()),
                }
    record["wall_s"] = round(time.time() - t0, 1)
    return record


def all_combos():
    for arch in configs.ARCH_IDS:
        for shape_name in INPUT_SHAPES:
            yield arch, shape_name


def main() -> None:
    jax.config.update("jax_num_cpu_devices", 512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--memory-only", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    combos = list(all_combos()) if args.all else [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in combos:
        mesh_name = "pod2" if args.multi_pod else "pod1"
        tag = f"{arch}_{shape_name}_{mesh_name}"
        try:
            rec = run_combo(arch, shape_name, multi_pod=args.multi_pod,
                            memory_only=args.memory_only)
        except Exception as e:  # a failure here is a bug in the system
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            failures.append(tag)
        (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
        status = rec.get("skipped") and "SKIP" or rec.get("error") and "FAIL" or "OK"
        extra = rec.get("skipped") or rec.get("error") or f"{rec.get('wall_s')}s"
        print(f"[{status:4s}] {tag}: {extra}", flush=True)

    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")


if __name__ == "__main__":
    main()
