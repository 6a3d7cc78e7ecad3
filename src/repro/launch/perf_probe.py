import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # fake host devices; never the TPU
"""Perf-iteration workbench: compile one (arch x shape x mesh) and break
the roofline terms down to the responsible HLO ops (with jax op metadata),
so each hillclimb hypothesis can be checked against the actual schedule.

    PYTHONPATH=src python -m repro.launch.perf_probe --arch X --shape Y \
        [--multi-pod] [--top 15]
"""
import argparse
import math
import re
from collections import defaultdict

import jax

from repro.configs import INPUT_SHAPES, config_for_shape
from repro.launch import roofline as R
from repro.launch.dryrun import build_lowering
from repro.launch.mesh import make_production_mesh, mesh_context

_META_RE = re.compile(r'op_name="([^"]+)"')


def top_ops(text, n_devices, default_trips, top=15):
    comps, entry = R.parse_hlo(text)
    mult = R._multipliers(comps, entry, default_trips)
    shapes = {}
    for comp in comps.values():
        for inst in comp.instructions:
            shapes[inst.name] = R._parse_dims(inst.typestr)
    colls, mems = [], []
    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m <= 0:
            continue
        for inst in comp.instructions:
            meta = _META_RE.search(inst.line)
            label = meta.group(1) if meta else inst.name
            if any(inst.opcode.startswith(c) for c in R.COLLECTIVES):
                out_b = R._parse_shape(inst.typestr)
                g = R._group_size(inst.line, n_devices)
                eff = out_b * (g - 1) / max(g, 1)
                if inst.opcode.startswith("all-reduce"):
                    eff *= 2
                colls.append((m * eff, m, inst.opcode, inst.typestr.split("{")[0],
                              g, label))
            elif not comp.is_fusion_body and inst.opcode in R.COUNT_BYTE_OPS:
                b = R._parse_shape(inst.typestr)
                mems.append((m * b, m, inst.opcode,
                             inst.typestr.split("{")[0], label))
    colls.sort(reverse=True)
    mems.sort(reverse=True)
    print(f"\n== top {top} collectives (bytes x mult) ==")
    for b, m, op, ty, g, label in colls[:top]:
        print(f"{b/1e9:9.2f} GB  x{m:6.0f}  {op:18s} g={g:<4d} {ty:28s} {label[:70]}")
    print(f"\n== top {top} memory ops (output bytes x mult) ==")
    for b, m, op, ty, label in mems[:top]:
        print(f"{b/1e9:9.2f} GB  x{m:6.0f}  {op:18s} {ty:28s} {label[:70]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    with mesh_context(mesh):
        lowered, meta = build_lowering(args.arch, args.shape, mesh)
        compiled = lowered.compile()
    txt = compiled.as_text()
    if args.save_hlo:
        open(args.save_hlo, "w").write(txt)
    cfg = config_for_shape(args.arch, args.shape)
    rep = R.analyze(txt, mesh.size, default_trips=max(1, cfg.n_periods))
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"terms: compute={rep.t_compute:.4f}s memory={rep.t_memory:.4f}s "
          f"collective={rep.t_collective:.4f}s bottleneck={rep.bottleneck}")
    print(f"peak mem/chip {peak/1e9:.2f} GB  (temp {mem.temp_size_in_bytes/1e9:.2f})")
    print(f"collectives by type: "
          f"{ {k: round(v/1e9,1) for k, v in rep.coll_by_type.items()} } GB")
    top_ops(txt, mesh.size, max(1, cfg.n_periods), args.top)


if __name__ == "__main__":
    main()
