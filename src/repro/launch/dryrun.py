import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax-importing import: jax locks device count and
# platform on init.  The fake host devices are the point, and a dry run
# must never take a TPU away from the process that serves on it.
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes with 512 placeholder host devices.

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # subprocess/combo

Per combo: jit(step).lower(ShapeDtypeStructs-with-shardings).compile(),
then record ``memory_analysis()`` (proves it fits), ``cost_analysis()``,
and the while-trip-scaled roofline terms parsed from the compiled HLO
(launch/roofline.py) into ``experiments/dryrun/<arch>__<shape>__<mesh>.json``
— the source of truth for EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, SKIPS,
                           config_for_shape)
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh, mesh_context
from repro.models import transformer as T
from repro.sharding import specs as SP
from repro.training import optimizer as O
from repro.training.trainer import make_train_step

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# gradient-accumulation factor per arch for train_4k (activation memory
# knob; chosen so memory_analysis peak fits 16GB/chip — EXPERIMENTS.md)
MICROBATCHES = {
    "smollm-360m": 2,
    # NB: global_batch/(micro*data_shards) must stay a positive integer.
    # 8 micro (2 samples/chip/microbatch): §Perf iteration 5 — halves the
    # per-microbatch FSDP gather traffic; fits after iterations 3-4 freed
    # ~4GB/chip.
    "command-r-plus-104b": 8,
    "mixtral-8x7b": 8,
    "recurrentgemma-9b": 8,
    "granite-moe-1b-a400m": 2,
    "stablelm-1.6b": 2,
    "qwen1.5-4b": 4,
    "phi-3-vision-4.2b": 4,
    "whisper-medium": 2,
    "xlstm-1.3b": 4,
}


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=jax.sharding.NamedSharding(mesh, spec))


def _with_shardings(shape_tree, spec_tree, mesh):
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp),
        shape_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _batch_structs(cfg, mesh, B, S, *, labels: bool):
    b_ax = SP.batch_spec(mesh, B)
    from jax.sharding import PartitionSpec as P

    out = {"tokens": _sds((B, S), jnp.int32, mesh, P(b_ax, None))}
    if labels:
        out["labels"] = _sds((B, S), jnp.int32, mesh, P(b_ax, None))
    if cfg.is_encoder_decoder:
        out["audio_embeds"] = _sds((B, cfg.encoder_seq, cfg.d_model),
                                   jnp.bfloat16, mesh, P(b_ax, None, None))
    if cfg.num_image_tokens:
        out["image_embeds"] = _sds((B, cfg.num_image_tokens, cfg.d_model),
                                   jnp.bfloat16, mesh, P(b_ax, None, None))
    return out


SEQ_SHARD_TRAIN = {"command-r-plus-104b"}
# bf16 AdamW moments for the largest config (EXPERIMENTS.md precision note)
BF16_MOMENTS = {"command-r-plus-104b"}


def build_lowering(arch: str, shape_name: str, mesh):
    """Returns (lowered, meta) for the right step fn for this shape kind."""
    cfg = config_for_shape(arch, shape_name)
    shp = INPUT_SHAPES[shape_name]
    B, S = shp.global_batch, shp.seq_len
    if shp.kind == "train" and arch in SEQ_SHARD_TRAIN:
        cfg = cfg.replace(act_seq_shard=True)
    if cfg.moe is not None:
        n_batch_shards = mesh.size // mesh.shape["model"]
        cfg = cfg.replace(moe_dispatch_groups=n_batch_shards)

    params_shapes = jax.eval_shape(
        lambda: T.init_model(jax.random.key(0), cfg))
    # decode: pure-TP weights when the TP shard fits comfortably (FSDP
    # would re-gather every weight every token — §Perf mixtral decode);
    # fall back to FSDP for params too big for a single chip's HBM.
    serve_tp_only = False
    if shp.kind == "decode":
        tp_bytes = 2 * T.count_params_analytic(cfg) / mesh.shape["model"]
        serve_tp_only = tp_bytes < 8e9
    meta0 = {"serve_tp_only": serve_tp_only}
    pspecs = SP.param_spec_tree(cfg, mesh, params_shapes,
                                serve_tp_only=serve_tp_only)
    params_in = _with_shardings(params_shapes, pspecs, mesh)

    meta = {"arch": arch, "shape": shape_name, "kind": shp.kind,
            "global_batch": B, "seq_len": S,
            "n_params": T.count_params_analytic(cfg), **meta0}

    if shp.kind == "train":
        micro = MICROBATCHES.get(arch, 1)
        # each microbatch must still split over every batch shard
        n_batch_shards = mesh.size // mesh.shape["model"]
        micro = max(1, min(micro, B // n_batch_shards))
        meta["microbatches"] = micro
        meta["act_seq_shard"] = cfg.act_seq_shard
        opt_cfg = O.OptimizerConfig(
            moment_dtype="bfloat16" if arch in BF16_MOMENTS else "float32")
        meta["moment_dtype"] = opt_cfg.moment_dtype
        opt_shapes = jax.eval_shape(lambda p: O.init_opt_state(p, opt_cfg),
                                    params_shapes)
        from jax.sharding import PartitionSpec as P

        ospecs = {"mu": pspecs, "nu": pspecs, "step": P()}
        opt_in = _with_shardings(opt_shapes, ospecs, mesh)
        batch_in = _batch_structs(cfg, mesh, B, S, labels=True)
        step = make_train_step(cfg, opt_cfg, microbatches=micro, remat=True)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
            params_in, opt_in, batch_in)
        meta["tokens_per_step"] = B * S
        return lowered, meta

    if shp.kind == "prefill":
        batch_in = _batch_structs(cfg, mesh, B, S, labels=False)

        def prefill_step(params, batch):
            return T.prefill(params, cfg, batch, S)

        # pin the output decode-state sharding (otherwise XLA may leave
        # the 27GB/chip KV stack unsharded on non-TP-divisible head counts)
        state_shapes = jax.eval_shape(lambda: T.init_decode_state(cfg, B, S))
        sspecs = SP.decode_state_spec_tree(cfg, mesh, B, state_shapes)
        sshard = jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp), sspecs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
        lowered = jax.jit(prefill_step,
                          out_shardings=(None, sshard)).lower(
            params_in, batch_in)
        meta["tokens_per_step"] = B * S
        return lowered, meta

    # decode: one new token against a seq_len-deep cache
    state_shapes = jax.eval_shape(
        lambda: T.init_decode_state(cfg, B, S))
    sspecs = SP.decode_state_spec_tree(cfg, mesh, B, state_shapes)
    state_in = _with_shardings(state_shapes, sspecs, mesh)
    from jax.sharding import PartitionSpec as P

    b_ax = SP.batch_spec(mesh, B)
    tok_in = _sds((B, 1), jnp.int32, mesh, P(b_ax, None))

    def serve_step(params, state, tokens):
        logits, new_state = T.decode_step(params, cfg, state, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], new_state

    lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(
        params_in, state_in, tok_in)
    meta["tokens_per_step"] = B
    return lowered, meta


def _tpu_peak_adjustment(meta, mesh, mem):
    """XLA-CPU upcasts every bf16 matmul to f32, materializing an f32
    shadow copy of each weight next to its bf16 argument (verified by
    buffer dump: f32 stacks exactly 2x their bf16 args).  TPUs execute
    bf16 matmuls natively, so for serve-TP decode we also report the peak
    with that shadow removed.  Train combos are left unadjusted (their
    f32 buffers include legitimate master/grad copies)."""
    if not meta.get("serve_tp_only"):
        return {}
    bf16_params = 2 * meta["n_params"] / mesh.shape["model"]
    shadow = 2.0 * bf16_params  # the f32 copy
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {"cpu_f32_weight_shadow_bytes": shadow,
            "peak_estimate_tpu_bytes": max(0.0, peak - shadow)}


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: Path
            ) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": SKIPS[(arch, shape_name)]}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.perf_counter()
    with mesh_context(mesh):
        lowered, meta = build_lowering(arch, shape_name, mesh)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    print(mem)
    ca = roofline.xla_cost_analysis(compiled)
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    cfg = config_for_shape(arch, shape_name)
    hlo = compiled.as_text()
    rep = roofline.analyze(hlo, n_dev, default_trips=max(1, cfg.n_periods))
    mf = roofline.model_flops(cfg, meta["tokens_per_step"],
                              "train" if meta["kind"] == "train" else "serve")

    result = {
        **meta,
        "mesh": mesh_name,
        "n_devices": n_dev,
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_analysis": {"flops": ca.get("flops"),
                          "bytes_accessed": ca.get("bytes accessed")},
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
            **_tpu_peak_adjustment(meta, mesh, mem),
        },
        "roofline": rep.to_dict(),
        "model_flops_global": mf,
        "useful_flops_ratio": mf / max(1.0, rep.flops * n_dev),
        "hlo_collective_ops": rep.coll_count,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    fn = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    fn.write_text(json.dumps(result, indent=1))
    print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
          f"compile={t_compile:.1f}s bottleneck={rep.bottleneck} "
          f"t=({rep.t_compute:.4f},{rep.t_memory:.4f},"
          f"{rep.t_collective:.4f})s")
    return result


def run_all(out_dir: Path, multi_pod_list=(False, True), archs=None,
            shapes=None) -> int:
    """Each combo in a subprocess (isolation + bounded memory)."""
    archs = archs or ASSIGNED_ARCHS
    shapes = shapes or list(INPUT_SHAPES)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in multi_pod_list:
                if (arch, shape) in SKIPS:
                    run_one(arch, shape, mp, out_dir)  # writes skip marker
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--out", str(out_dir)]
                if mp:
                    cmd.append("--multi-pod")
                r = subprocess.run(cmd, capture_output=True, text=True)
                tail = (r.stdout + r.stderr).strip().splitlines()[-3:]
                if r.returncode != 0:
                    failures.append((arch, shape, mp))
                    print(f"[dryrun] FAIL {arch} x {shape} mp={mp}:")
                    print("\n".join(tail))
                else:
                    print("\n".join(t for t in tail if "[dryrun]" in t))
    print(f"[dryrun] done, {len(failures)} failures: {failures}")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    args = ap.parse_args()
    out = Path(args.out)
    if args.all:
        sys.exit(run_all(out))
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    skip = (args.arch, args.shape)
    if skip in SKIPS:
        print(f"[dryrun] SKIP {skip}: {SKIPS[skip]}")
        out.mkdir(parents=True, exist_ok=True)
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        (out / f"{args.arch}__{args.shape}__{mesh_name}.json").write_text(
            json.dumps({"arch": args.arch, "shape": args.shape,
                        "mesh": mesh_name, "status": "skipped",
                        "reason": SKIPS[skip]}, indent=1))
        return
    run_one(args.arch, args.shape, args.multi_pod, out)


if __name__ == "__main__":
    main()
