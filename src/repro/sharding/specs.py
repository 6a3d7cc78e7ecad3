"""Logical-axis sharding rules (FSDP + tensor parallel + expert parallel).

The production mesh is ``("data", "model")`` single-pod or
``("pod", "data", "model")`` multi-pod (launch/mesh.py).  Policy:

* **batch** -> ``("pod", "data")`` (dropped when the global batch is not
  divisible, e.g. long_500k B=1);
* **tensor parallel** -> ``"model"`` on attention head axes / FFN hidden /
  expert hidden, guarded by divisibility (e.g. smollm's 15 heads and
  qwen's 20 heads do not TP on a 16-way axis — their FFN still does);
* **FSDP** -> parameters additionally sharded on ``"data"`` along a
  non-TP axis so params+AdamW state of the 104B config fit 16GB/chip;
* **expert parallel** -> expert axis on ``"model"`` when
  ``num_experts % model_size == 0`` (granite: 32 % 16 = 0 -> EP with
  all-to-all dispatch); otherwise experts are tensor-parallel over their
  hidden dim (mixtral: 8 experts on a 16-way axis -> TP).

``constrain`` is a mesh-aware ``with_sharding_constraint`` that silently
no-ops outside a mesh context (CPU unit tests) and drops axes that are
absent or non-divisible, so model code can state intent unconditionally.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def _current_mesh():
    """The ambient mesh installed by ``launch/mesh.mesh_context``, or
    None outside one (CPU unit tests)."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return None
    return m


def _filter_spec(mesh, shape, spec_entries):
    """Keep only axes present in the mesh and dividing the dim size."""
    out = []
    for dim, entry in zip(shape, spec_entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        size = 1
        for ax in axes:
            if ax in mesh.axis_names:
                kept.append(ax)
                size *= mesh.shape[ax]
        if kept and dim % size == 0:
            out.append(tuple(kept) if len(kept) > 1 else kept[0])
        else:
            out.append(None)
    return P(*out)


def constrain(x, *spec_entries):
    mesh = _current_mesh()
    if mesh is None:
        return x
    entries = list(spec_entries) + [None] * (x.ndim - len(spec_entries))
    spec = _filter_spec(mesh, x.shape, entries[: x.ndim])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


# ----------------------------------------------------------------------
# Parameter / activation spec construction (used by the launchers).
def batch_spec(mesh, global_batch: int):
    """Spec entry for the batch axis: ("pod","data") when divisible."""
    axes = [ax for ax in ("pod", "data") if ax in mesh.axis_names]
    size = 1
    for ax in axes:
        size *= mesh.shape[ax]
    if axes and global_batch % size == 0:
        return tuple(axes) if len(axes) > 1 else axes[0]
    return None


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _tp(mesh, dim: int) -> Optional[str]:
    return "model" if dim % mesh_axis_size(mesh, "model") == 0 else None


def _fsdp(mesh, dim: int):
    """FSDP axis for parameters/optimizer state: all batch-parallel axes
    (ZeRO shards over every data rank, pods included)."""
    axes = tuple(ax for ax in ("data", "pod") if ax in mesh.axis_names)
    size = 1
    for ax in axes:
        size *= mesh.shape[ax]
    if axes and dim % size == 0:
        return axes if len(axes) > 1 else axes[0]
    if dim % mesh_axis_size(mesh, "data") == 0:
        return "data"
    return None


def param_spec_tree(cfg, mesh, params_shape, *, serve_tp_only: bool = False):
    """PartitionSpec pytree mirroring ``init_model``'s parameter tree.

    Works from the *shape* tree (``jax.eval_shape`` output) so no real
    arrays are needed.  Rules are name-based on the leaf path.

    ``serve_tp_only`` drops the FSDP ("data") axis from weights — for
    autoregressive decoding, FSDP means re-all-gathering every weight
    EVERY TOKEN (10.5GB/step on mixtral decode_32k — §Perf); serving uses
    pure tensor parallelism whenever the TP-sharded params fit HBM.
    """
    msz = mesh_axis_size(mesh, "model")
    ep = cfg.moe is not None and cfg.moe.num_experts % msz == 0

    fsdp_fn = _fsdp
    if serve_tp_only:
        def fsdp_fn(mesh_, dim_):
            return None

    def leaf_spec(path: Tuple[str, ...], shape) -> P:
        name = path[-1]
        nd = len(shape)
        stacked = nd >= 1 and path_is_stacked(path)
        pre = (None,) if stacked else ()
        core = shape[1:] if stacked else shape

        def sp(*entries):
            entries = list(entries) + [None] * (len(core) - len(entries))
            return P(*(pre + tuple(entries[: len(core)])))

        # --- embeddings / unembedding ---
        if name == "table":
            return P(_tp(mesh, shape[0]), fsdp_fn(mesh, shape[1]))
        if name == "w" and "lm_head" in path:
            return P(fsdp_fn(mesh, shape[0]), _tp(mesh, shape[1]))
        # --- attention (D, H, hd) / (H, hd, D) ---
        if name in ("wq", "wk", "wv"):
            d, h = core[0], core[1]
            tp_h = _tp(mesh, h)
            if tp_h is None:
                # GQA kv heads not divisible by the model axis: shard the
                # contraction dim D on "model" instead (partial-sum AR of
                # the small kv activations replaces the 936GB/step
                # replicated-weight-grad AR — §Perf iteration 4, 104B)
                f = fsdp_fn(mesh, d)
                fax = f if isinstance(f, tuple) else ((f,) if f else ())
                comb = tuple(fax) + ("model",)
                sz = 1
                for ax in comb:
                    sz *= mesh_axis_size(mesh, ax)
                if d % sz == 0:
                    return sp(comb, None, None)
            return sp(fsdp_fn(mesh, d), tp_h, None)
        if name == "wo":
            h, _, d = core
            return sp(_tp(mesh, h), None, fsdp_fn(mesh, d))
        if name in ("bq", "bk", "bv"):
            return sp(_tp(mesh, core[0]), None)
        # --- dense MLP ---
        if name in ("w_gate", "w_up", "w_in") and "experts" not in path:
            return sp(fsdp_fn(mesh, core[0]), _tp(mesh, core[1]))
        if name in ("w_down", "w_out") and "experts" not in path:
            return sp(_tp(mesh, core[0]), fsdp_fn(mesh, core[1]))
        # --- MoE experts (E, D, F) / (E, F, D) ---
        if "experts" in path and name in ("w_gate", "w_up"):
            e, d, f = core
            if ep:
                return sp("model", fsdp_fn(mesh, d), None)
            return sp(None, fsdp_fn(mesh, d), _tp(mesh, f))
        if "experts" in path and name == "w_down":
            e, f, d = core
            if ep:
                return sp("model", None, fsdp_fn(mesh, d))
            return sp(None, _tp(mesh, f), fsdp_fn(mesh, d))
        if name == "router":
            return sp(fsdp_fn(mesh, core[0]), None)
        # --- recurrent blocks ---
        if name in ("w_qkv",):  # (H, dh, dh) blockdiag
            return sp(None, None, _tp(mesh, core[2]))
        if name in ("w_gates_in",):  # (D, n_gates, H, dh)
            return sp(fsdp_fn(mesh, core[0]), None, None, None)
        if name in ("r_gates",):  # (n_gates, H, dh, dh)
            return sp(None, None, None, _tp(mesh, core[3]))
        if name in ("w_x", "w_gate_br", "w_in_gate", "w_rec_gate", "w_ogate"):
            return sp(fsdp_fn(mesh, core[0]), _tp(mesh, core[1]) if len(core) > 1 else None)
        if name in ("w_out_r", "w_out_x", "out_proj"):
            return sp(_tp(mesh, core[0]), fsdp_fn(mesh, core[1]) if len(core) > 1 else None)
        if name == "img_proj":
            return sp(fsdp_fn(mesh, core[0]), None)
        # scales, biases, conv kernels, lambdas, norms: replicate
        return P(*([None] * nd))

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = [walk(v, path + (str(i),)) for i, v in enumerate(tree)]
            return type(tree)(t)
        return leaf_spec(path, tree.shape)

    return walk(params_shape, ())


def path_is_stacked(path: Tuple[str, ...]) -> bool:
    """Leaves under params["stack"] carry a leading n_periods axis."""
    return "stack" in path


def decode_state_spec_tree(cfg, mesh, global_batch: int, state_shapes):
    """PartitionSpec tree for the decode state (KV caches / recurrent).

    Policy: batch on ("pod","data") when divisible; KV heads on "model"
    when divisible, else the cache sequence axis on "model"; for B==1
    (long_500k) the cache sequence axis additionally takes the batch axes
    (sequence-parallel cache).  Recurrent states shard their elementwise
    feature axis on "model".
    """
    b_ax = batch_spec(mesh, global_batch)
    msz = mesh_axis_size(mesh, "model")

    def kv_spec(shape, lead):
        B, W, Hkv, hd = shape[-4:]
        h_ax = "model" if Hkv % msz == 0 else None
        w_axes = []
        if b_ax is None:
            cand = [ax for ax in ("pod", "data") if ax in mesh.axis_names]
            sz = 1
            for ax in cand:
                sz *= mesh.shape[ax]
            if cand and W % sz == 0:
                w_axes += cand
        if h_ax is None and W % (msz * max(1, math_prod(mesh, w_axes))) == 0:
            w_axes.append("model")
        w = tuple(w_axes) if len(w_axes) > 1 else (w_axes[0] if w_axes else None)
        return P(*(lead + (b_ax, w, h_ax, None)))

    def pos_spec(shape, lead, sibling_kv_shape):
        return P(*(lead + (None,) * (len(shape) - len(lead))))

    def leaf(path, shape):
        lead = (None,) if ("stack" in path or "enc_kv" in path) else ()
        name = path[-1]
        core = shape[len(lead):]
        if name in ("k", "v") and ("kv" in path or "enc_kv" in path) \
                and len(core) >= 4:
            return kv_spec(shape, lead)
        if name == "pos":
            if len(core) == 2:  # per-row (B, W) ring position map: shard
                # batch with the sibling k/v, replicate the ring axis
                return P(*(lead + (b_ax, None)))
            return P(*([None] * len(shape)))
        # recurrent states: shard trailing feature axis on model if divisible
        if name in ("h", "c", "n", "m", "C", "conv", "rec"):
            entries = [b_ax] + [None] * (len(core) - 1)
            if len(core) >= 2 and core[-1] % msz == 0:
                entries[-1] = "model"
            return P(*(lead + tuple(entries)))
        entries = [b_ax] + [None] * (len(core) - 1)
        return P(*(lead + tuple(entries)))

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, path + (str(i),))
                              for i, v in enumerate(tree))
        return leaf(path, tree.shape)

    return walk(state_shapes, ())


def math_prod(mesh, axes):
    out = 1
    for ax in axes:
        out *= mesh.shape[ax]
    return out


def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )
