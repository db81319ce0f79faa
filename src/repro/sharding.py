"""Per-arch sharding policy (DESIGN.md §4).

Everything here produces PartitionSpec pytrees matching the params / batch /
cache structures. Rules are path-aware (Megatron TP alternation: column-
parallel QKV/up/gate, row-parallel O/down → one all-reduce per block) and
divisibility-aware (jit inputs must shard evenly; intermediates may pad).

FSDP (ZeRO-3) additionally shards a second weight dim over the data axis —
required to fit the 1T-param configs; XLA all-gathers per scanned layer and
reduce-scatters gradients.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

__all__ = ["ShardingPolicy", "make_policy"]

#: params whose name picks row-parallel (shard input dim over model)
_ROW_PARALLEL = {"wo", "down", "down_proj", "out_proj"}
#: column-parallel (shard output dim over model)
_COL_PARALLEL = {"wq", "wk", "wv", "up", "gate", "in_proj", "w_in", "x_proj",
                 "dt_proj", "w_gates", "head"}
#: replicated regardless of size
_REPLICATED = {"norm1", "norm2", "norm_x", "final_norm", "enc_norm", "router",
               "conv_w", "conv_b", "A_log", "D", "out_norm_g", "r", "b", "g"}
#: MoE stacked experts: expert dim shards over model (expert parallelism)
_EXPERT = {"moe"}


def _path_keys(path) -> list[str]:
    out = []
    for k in path:
        key = getattr(k, "key", None)
        if key is None:
            key = getattr(k, "name", None)
        if key is None and hasattr(k, "idx"):
            key = str(k.idx)
        out.append(str(key))
    return out


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


class ShardingPolicy:
    """parallelism:
      * "tp"   — Megatron TP over the model axis + DP(/FSDP) over data.
        Required for serving (latency) and for archs whose layer doesn't
        fit one chip (MoE giants).
      * "fsdp" — pure ZeRO-3: batch shards over EVERY axis (incl. model),
        parameters fully shard and are all-gathered per layer; there are NO
        activation collectives. For <=30B trains at global_batch >= chips
        this cuts per-layer collective bytes ~12x vs tp (EXPERIMENTS.md
        §Perf iter 6) — per-layer param gathers are small next to SP
        activation gathers at 4k-token/chip batches.
    """

    def __init__(self, cfg: ArchConfig, mesh: Mesh, *, fsdp: bool = False,
                 parallelism: str = "tp", quantized_serving: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.fsdp = fsdp or parallelism == "fsdp"
        self.parallelism = parallelism
        axes = dict(mesh.shape)
        if parallelism == "replicated":
            # CP serving: quantized weights are small enough to live on
            # every chip — zero weight collectives, and token-sharded
            # activations never conflict with weight shardings (GSPMD's
            # "involuntary full rematerialization" cascade, §Perf cell 2)
            self.model_axis = "model"
            self.n_model = 1
            self.data_axes = tuple(a for a in ("pod", "data") if a in axes)
            self.fsdp_axes = ()
            self.fsdp = False
        elif parallelism == "fsdp":
            self.model_axis = None
            self.n_model = 1
            self.data_axes = tuple(a for a in ("pod", "data", "model")
                                   if a in axes)
            self.fsdp_axes = tuple(a for a in ("data", "model") if a in axes)
        else:
            self.model_axis = "model"
            self.n_model = axes["model"]
            self.data_axes = tuple(a for a in ("pod", "data") if a in axes)
            # FSDP normally shards over 'data' only (params never cross
            # DCN); the 398B/1T giants don't fit one pod's HBM, so for them
            # ZeRO-3 extends over the pod axis too (per-layer all-gathers
            # cross DCN — the documented cost of fitting 1T params at all)
            giant = cfg.param_count_estimate() > 100e9
            self.fsdp_axes = (self.data_axes if ("pod" in axes and giant)
                              else tuple(a for a in ("data",) if a in axes))
        self.n_data = int(np.prod([axes[a] for a in self.data_axes]))
        self.n_fsdp = int(np.prod([axes[a] for a in self.fsdp_axes])) or 1

    # -- leaf rules ---------------------------------------------------------

    def _weight_spec(self, keys: list[str], shape: tuple) -> P:
        """Spec for one array leaf of the params pytree. Stacked leading
        period/expert dims are detected by path context."""
        nd = len(shape)
        name_hits = set(keys)
        # embedding table
        if "table" in name_hits:
            v, d = shape[-2], shape[-1]
            fx = (self.fsdp_axes if len(self.fsdp_axes) > 1
                  else (self.fsdp_axes[0] if self.fsdp_axes else None))
            if self.n_model > 1 and _div(v, self.n_model):
                spec = [None] * (nd - 2) + [self.model_axis, None]
            elif self.n_model > 1 and _div(d, self.n_model):
                spec = [None] * (nd - 2) + [None, self.model_axis]
            elif self.fsdp and _div(v, self.n_fsdp):
                spec = [None] * (nd - 2) + [fx, None]
            elif self.fsdp and _div(d, self.n_fsdp):
                spec = [None] * (nd - 2) + [None, fx]
            else:
                spec = [None] * nd
            return P(*spec)
        if "router" in name_hits:
            return P(*([None] * nd))    # tiny; shard_map expects replicated
        if name_hits & _REPLICATED and not (name_hits & {"moe"}):
            return P(*([None] * nd))
        if nd < 2:
            return P(*([None] * nd))

        spec: list = [None] * nd
        # MoE experts: (P?, E, D, F) — expert dim over model
        if name_hits & _EXPERT and nd >= 3 \
                and not (name_hits & _REPLICATED):
            # find the expert dim: first dim equal to n_experts
            for i, s in enumerate(shape):
                if s == self.cfg.n_experts and _div(s, self.n_model):
                    spec[i] = self.model_axis
                    break
            else:
                return self._tp_spec(keys, shape)
            if self.fsdp:
                # shard d_ff (largest remaining divisible dim) over the
                # fsdp axes
                cands = [(s, i) for i, s in enumerate(shape)
                         if spec[i] is None and _div(s, self.n_fsdp)]
                if cands:
                    _, i = max(cands)
                    spec[i] = (self.fsdp_axes if len(self.fsdp_axes) > 1
                               else self.fsdp_axes[0])
            return P(*spec)
        return self._tp_spec(keys, shape)

    def _tp_spec(self, keys: list[str], shape: tuple) -> P:
        nd = len(shape)
        name_hits = set(keys)
        spec: list = [None] * nd
        # pick TP dim: row-parallel -> -2, column-parallel -> -1, else largest
        tp_dim = None
        if name_hits & _ROW_PARALLEL and nd >= 2:
            tp_dim = nd - 2
        elif name_hits & _COL_PARALLEL:
            tp_dim = nd - 1
        if tp_dim is not None and not _div(shape[tp_dim], self.n_model):
            tp_dim = None
        if tp_dim is None:
            cands = [(s, i) for i, s in enumerate(shape[-2:], start=nd - 2)
                     if _div(s, self.n_model)]
            if cands:
                _, tp_dim = max(cands)
        if tp_dim is not None:
            spec[tp_dim] = self.model_axis
        if self.fsdp and nd >= 2 and self.fsdp_axes:
            cands = [(s, i) for i, s in enumerate(shape)
                     if spec[i] is None and i >= nd - 2
                     and _div(s, self.n_fsdp)]
            if cands:
                _, i = max(cands)
                spec[i] = (self.fsdp_axes if len(self.fsdp_axes) > 1
                           else self.fsdp_axes[0])
        return P(*spec)

    # -- pytree walkers ------------------------------------------------------

    def param_specs(self, params_shape: Any):
        """PartitionSpec pytree mirroring `params_shape` (ShapeDtypeStructs
        or arrays; QuantizedTensors descend to codes/scale leaves)."""
        def leaf(path, x):
            if self.parallelism == "replicated":
                return P(*([None] * len(x.shape)))
            keys = _path_keys(path)
            spec = self._weight_spec(keys, tuple(x.shape))
            # quantized codes on a packed dim: the packed (last) dim is N/2 —
            # divisibility already checked against the code shape itself.
            return spec
        return jax.tree_util.tree_map_with_path(leaf, params_shape)

    def cache_specs(self, caches_shape: Any):
        dp = self.data_axes

        def leaf(path, x):
            keys = _path_keys(path)
            shape = tuple(x.shape)
            nd = len(shape)
            kv_key = keys[-1] in ("k", "v", "xk", "xv") or (
                len(keys) >= 2 and keys[-2] in ("k", "v")
                and keys[-1] in ("codes", "scale"))
            if kv_key:
                # (P, B, Hkv, S, dh|1): seq over model (flash-decode CP);
                # int8-quantized caches have codes+scale leaves
                spec = [None, dp, None, self.model_axis, None]
                if not _div(shape[3], self.n_model):
                    spec[3] = None
                if not _div(shape[1], self.n_data):
                    spec[1] = self._batch_axes(shape[1])
                return P(*spec)
            # ssm states: (P, B, ...): batch over data; largest divisible
            # trailing dim over model
            spec = [None] * nd
            spec[1] = self._batch_axes(shape[1])
            cands = [(s, i) for i, s in enumerate(shape[2:], start=2)
                     if _div(s, self.n_model)]
            if cands:
                _, i = max(cands)
                spec[i] = self.model_axis
            return P(*spec)

        return jax.tree_util.tree_map_with_path(leaf, caches_shape)

    def paged_state_specs(self, caches: Any):
        """Specs for the serving engine's paged StateCache pytree.

        Token-KV page pools (``kp``/``vp``, ``(P, n_pages, Hkv, page_size,
        dh)``, a quantized pool's scales ``(P, n_pages, Hkv, lanes)``)
        and read-only cross entries (``xk``/``xv``, ``(P, n_cross,
        Hkv, S_enc, dh)``) shard their KV-head axis over the model axis
        when it divides — including the ``codes``/``scale`` children of a
        quantized pool, which share the head axis. Everything else
        (recurrent slabs, conv states) is per-sequence with no head axis
        and replicates. Block tables and write cursors live host-side and
        never enter this tree."""
        pool_keys = ("kp", "vp", "xk", "xv")

        def leaf(path, x):
            keys = _path_keys(path)
            shape = tuple(x.shape)
            nd = len(shape)
            kv_key = keys[-1] in pool_keys or (
                len(keys) >= 2 and keys[-2] in pool_keys
                and keys[-1] in ("codes", "scale"))
            spec = [None] * nd
            if kv_key and nd >= 4 and self.n_model > 1 \
                    and _div(shape[2], self.n_model):
                spec[2] = self.model_axis
            return P(*spec)

        return jax.tree_util.tree_map_with_path(leaf, caches)

    def _batch_axes(self, b: int):
        """Largest prefix of data axes that divides the batch."""
        axes = []
        rem = b
        for a in self.data_axes:
            n = dict(self.mesh.shape)[a]
            if rem % n == 0:
                axes.append(a)
                rem //= n
        if not axes:
            return None
        return tuple(axes) if len(axes) > 1 else axes[0]

    def batch_spec(self, b: int, extra_dims: int = 1):
        return P(self._batch_axes(b), *([None] * extra_dims))

    def opt_specs(self, params_shape: Any, opt_shape: Any):
        """Optimizer state mirrors param specs leaf-for-leaf where shapes
        match; scalars replicate."""
        pspecs = self.param_specs(params_shape)

        def match(ps, os_leaf_shape):
            return ps

        # momenta trees share param structure; walk both together
        def leaf(path, x):
            keys = _path_keys(path)
            if len(x.shape) == 0:
                return P()
            return self._weight_spec([k for k in keys if k not in
                                      ("mu", "nu", "m", "v", "ef")],
                                     tuple(x.shape))
        return jax.tree_util.tree_map_with_path(leaf, opt_shape)

    def named(self, spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, P))


def make_policy(cfg: ArchConfig, mesh: Mesh, **kw) -> ShardingPolicy:
    if kw.get("parallelism") == "fsdp":
        return ShardingPolicy(cfg, mesh, **kw)
    if "fsdp" not in kw:
        # FSDP (ZeRO-3) by default above 2B params: per-layer all-gathers
        # overlap with compute under the latency-hiding scheduler, and the
        # 16x reduction in resident params/optimizer is what fits the 8-15B
        # dense configs (and is mandatory for the 398B/1T giants)
        kw["fsdp"] = cfg.param_count_estimate() > 2e9
    return ShardingPolicy(cfg, mesh, **kw)
