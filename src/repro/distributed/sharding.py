"""Logical-axis sharding (MaxText-style rules → PartitionSpecs).

Models call ``shard_act(x, "btd")`` with a logical activation layout name;
outside a mesh context this is a no-op (smoke tests see 1 device), inside
``use_mesh(mesh)`` it becomes with_sharding_constraint with the rules below.

Param shardings are derived from leaf path names (``param_shardings``):
tensor-parallel on the ``model`` axis (heads / ffn / experts / vocab),
optionally FSDP on ``data`` for the largest axis.
"""
from __future__ import annotations

import contextlib
import re
import threading

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()

# activation layouts: batch is sharded over every data-parallel axis,
# heads/vocab/ffn over "model"; long-context KV shards sequence over "data"
ACT_RULES = {
    "btd": lambda dp: P(dp, None, None),
    # Megatron-SP: residual stream sharded over (batch→dp, seq→model) —
    # activation memory /16 between blocks, TP all-reduces become
    # reduce-scatter + all-gather pairs. Toggled via set_sequence_parallel.
    "btd_sp": lambda dp: P(dp, "model", None),
    "btv": lambda dp: P(dp, None, "model"),
    "bthd": lambda dp: P(dp, None, "model", None),
    "kv_seq": lambda dp: P(None, "data", "model", None),
    "moe_ecd": lambda dp: P(None, dp, None),   # (experts, capacity, d)
    "td": lambda dp: P(dp, None),
}

_SEQ_PARALLEL = False


def set_sequence_parallel(on: bool):
    global _SEQ_PARALLEL
    _SEQ_PARALLEL = on


def _dp_axes(mesh: Mesh):
    axes = [a for a in mesh.axis_names if a in ("pod", "data")]
    if len(axes) == 1:
        return axes[0]
    return tuple(axes)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Make ``mesh`` the thread's logical-sharding mesh (``shard_act``
    reads it). Callers still enter ``mesh`` itself around ``jax.jit``."""
    prev = getattr(_STATE, "mesh", None)
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.mesh = prev


def current_mesh() -> Mesh | None:
    return getattr(_STATE, "mesh", None)


def row_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Row-sharded (N, d) placement for database matrices — the layout the
    serving column store and the distributed tournament scan agree on."""
    return NamedSharding(mesh, P(axis, None))


def shard_act(x: jnp.ndarray, layout: str) -> jnp.ndarray:
    mesh = current_mesh()
    if mesh is None:
        return x
    if layout == "btd" and _SEQ_PARALLEL and x.ndim >= 2 \
            and x.shape[1] % mesh.shape.get("model", 1) == 0:
        layout = "btd_sp"
    dp = _dp_axes(mesh)
    spec = ACT_RULES[layout](dp)
    if len(spec) != x.ndim:
        # pad spec with None for trailing dims (e.g. logits (B, S, V))
        spec = P(*(list(spec) + [None] * (x.ndim - len(spec)))) \
            if x.ndim > len(spec) else P(*tuple(spec)[: x.ndim])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---- parameter sharding rules (path-keyword -> trailing-dim base spec) ----
#
# Base specs cover the TRAILING dims of the (possibly layer-stacked) tensor;
# leading stacked axes are padded with None. Tensor-parallel on "model":
# column-parallel for up/qkv/gate projections, row-parallel for
# down/out projections. MoE experts use expert-tensor-parallelism (expert
# d_ff over "model") because granite's 40/32 expert counts don't divide 16.
PARAM_RULES: list[tuple[str, tuple]] = [
    (r"conv_w$|conv_b$|A_log$|/D$|dt_bias$|bias$|ln|norm|scale$|f_bias$|"
     r"r_rec$|router$|i_gate$|f_gate$", ()),               # replicated
    (r"wq$|wk$|wv$|/q$|/k$|/v$|w_gate$|w_up$|up_proj$|in_proj$|w_in$",
     (None, "model")),
    (r"wo$|out_proj$|down_proj$|w_down$", ("model", None)),
    (r"embed$|lm_head$", (None, "model")),
    (r"b[qkv]$", ("model",)),
]


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_spec(path, leaf) -> P:
    s = "/" + _path_str(path)
    nd = leaf.ndim
    for pat, base in PARAM_RULES:
        if re.search(pat, s):
            base = tuple(base)
            if len(base) > nd:
                base = base[-nd:]
            return P(*((None,) * (nd - len(base)) + base))
    return P(*([None] * nd))


def param_spec_fsdp(path, leaf, mesh: Mesh) -> P:
    """FSDP: shard each tensor's largest divisible dim over ALL mesh axes
    (fall back to the data axes, then to replication). Activations stay
    batch-sharded; per-layer param all-gathers replace the per-token TP
    all-reduces — the winning trade at large token batches (§Perf)."""
    all_axes = tuple(mesh.axis_names)
    sizes = [int(np.prod([mesh.shape[a] for a in gruppe]))
             for gruppe in (all_axes,)]
    candidates = [all_axes,
                  tuple(a for a in all_axes if a != "model") or all_axes]
    nd = leaf.ndim
    if nd == 0:
        return P()
    order = sorted(range(nd), key=lambda ax: -leaf.shape[ax])
    for axes in candidates:
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        for ax in order:
            if leaf.shape[ax] % total == 0 and leaf.shape[ax] >= total:
                spec = [None] * nd
                spec[ax] = axes if len(axes) > 1 else axes[0]
                return P(*spec)
    return P(*([None] * nd))


import numpy as np  # noqa: E402  (used by param_spec_fsdp)


def param_shardings(params, mesh: Mesh, mode: str = "tp"):
    if mode == "fsdp":
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(mesh, param_spec_fsdp(path, leaf, mesh)),
            params)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(path, leaf)), params)


def cache_shardings(cache, mesh: Mesh, *, shard_seq: bool = False):
    """Decode-cache shardings.

    KV tensors (L, B, S, H, d): batch over the data axes when divisible;
    the cache SEQUENCE is sharded over "model" (flash-decoding style — each
    model shard owns a KV slice and attention combines partial softmax
    stats), which works for every kv-head count (4/8/16/32 all fail to
    divide 16 for some arch). ``shard_seq`` (long-context, batch=1) spreads
    the sequence over ALL axes. State caches (SSM/xLSTM) shard batch only.
    """
    dp = _dp_axes(mesh)
    dp_axes = dp if isinstance(dp, tuple) else (dp,)
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]
    model = mesh.shape.get("model", 1)

    def spec_for(path, leaf):
        nd = leaf.ndim
        s = _path_str(path)
        if re.search(r"k_scale$|v_scale$", s) and nd == 4:
            L, B, S, H = leaf.shape
            if B % dp_total == 0 and S % model == 0:
                return NamedSharding(mesh, P(None, dp, "model", None))
            return NamedSharding(mesh, P(*([None] * nd)))
        if re.search(r"/k$|/v$|/ck$|/cv$", "/" + s) and nd == 5:
            L, B, S, H, hd = leaf.shape
            batch_ok = B % dp_total == 0
            if shard_seq or not batch_ok:
                seq_axes = tuple(dp_axes) + ("model",)
                total = dp_total * model
                if S % total == 0:
                    return NamedSharding(mesh, P(None, None, seq_axes, None, None))
                if S % model == 0:
                    return NamedSharding(mesh, P(None, None, "model", None, None))
                return NamedSharding(mesh, P(*([None] * nd)))
            if S % model == 0:
                return NamedSharding(mesh, P(None, dp, "model", None, None))
            return NamedSharding(mesh, P(None, dp, None, None, None))
        # ssm / lstm state tensors: find the batch-sized axis and shard it
        # over data when divisible (batch follows the layer-stack axes)
        spec = [None] * nd
        if nd >= 3:
            for ax in range(1, nd - 1):
                if leaf.shape[ax] % dp_total == 0 and leaf.shape[ax] >= dp_total:
                    spec[ax] = dp
                    break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(spec_for, cache)
