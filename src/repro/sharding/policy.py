"""Sharding policy: per-tensor PartitionSpecs for params, inputs, caches,
and activation constraints, with divisibility checks + documented fallbacks.

Axes
----
``model``  tensor/expert parallel:
    * FFN hidden dim, MoE expert dim (EP when n_experts % model == 0, else
      TP-within-expert on expert d_ff), vocab dim of embed/unembed,
    * attention Q/KV head dims when divisible by the axis — otherwise the
      policy falls back to *context parallelism*: attention weights stay
      replicated on `model` and the sequence dim of activations is sharded
      (recorded in ``self.fallbacks``),
    * RG-LRU width, SSD head_dim (both elementwise over channels).
``data`` (+ ``pod``)  batch parallel for activations; ZeRO/FSDP shard for
    params & optimizer state (largest replicated dim, when divisible).

The residual stream is sequence-sharded over ``model`` between blocks
(Megatron sequence parallelism) so per-layer remat residuals fit HBM.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def _divisible(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


@dataclass
class ShardingPolicy:
    mesh: Mesh
    cfg: ModelConfig
    seq_parallel: bool = True
    fsdp: bool = True
    # --- beyond-paper perf levers (launch/dryrun.py's lever flags) ---
    # pad attention heads with zero heads up to the next mesh-divisible
    # count so head-TP applies where context-parallelism would otherwise be
    # forced (exact: zero wo rows null the padded heads' contribution).
    pad_heads: bool = False
    max_pad_overhead: float = 1.5
    # chunk the query dim of global causal attention (lax.map over chunks)
    # to cap the [B,H,Sq,Sk] scores buffer.
    attn_q_chunk: int = 0
    fallbacks: List[str] = field(default_factory=list)

    # -------------------------------------------------------------- helpers
    @property
    def batch_axes(self):
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def model_size(self) -> int:
        return self.mesh.shape["model"]

    @property
    def data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    @property
    def fsdp_axis(self):
        return "data" if ("data" in self.mesh.axis_names and self.fsdp) else None

    def ns(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def _c(self, x, *spec):
        return jax.lax.with_sharding_constraint(x, self.ns(*spec))

    @property
    def attn_head_sharded(self) -> bool:
        return _divisible(self.cfg.n_heads, self.model_size)

    @property
    def kv_head_sharded(self) -> bool:
        return _divisible(self.cfg.n_kv_heads, self.model_size)

    @property
    def expert_parallel(self) -> bool:
        moe = self.cfg.moe
        return moe is not None and _divisible(moe.n_experts, self.model_size)

    def head_padding(self):
        """(Hp, Hkvp) padded head counts enabling head-TP, or None.

        Preserves the GQA ratio G = H/Hkv so real query head h keeps its kv
        head h // G; padded heads produce zero contribution because their
        wo rows are zero. Only applied when the FLOP overhead Hp/H stays
        under max_pad_overhead (e.g. qwen 40->48: 1.2x attention; gemma3
        4->16 q-heads with kv 1->4: 4x but attention is a small fraction;
        recurrentgemma 10->80 would be 8x: rejected -> CP fallback)."""
        if not self.pad_heads or self.cfg.n_heads == 0 or \
                self.attn_head_sharded:
            return None
        H, Hkv, ms = self.cfg.n_heads, self.cfg.n_kv_heads, self.model_size
        G = H // Hkv
        Hkvp = Hkv
        while (G * Hkvp) % ms != 0:
            Hkvp += 1
            if G * Hkvp > H * self.max_pad_overhead:
                return None
        return G * Hkvp, Hkvp

    def moe_sharded(self, cfg) -> bool:
        """Use the shard_map dispatch (EP or TP-within-expert)."""
        moe = cfg.moe
        if moe is None:
            return False
        return _divisible(moe.n_experts, self.model_size) or \
            _divisible(moe.d_ff, self.model_size)

    # ------------------------------------------------------------- params
    def _param_spec(self, name: str, shape: Tuple[int, ...], stacked: bool
                    ) -> P:
        """Spec for one weight. ``stacked`` = leading scan-cycle dim."""
        cfg = self.cfg
        ms = self.model_size
        lead = (None,) if stacked else ()
        body = shape[1:] if stacked else shape
        fa = self.fsdp_axis

        def fs(dim_idx_in_body, current):
            """apply FSDP axis to dim if free & divisible."""
            if fa is None:
                return current
            out = list(current)
            if out[dim_idx_in_body] is None and \
                    _divisible(body[dim_idx_in_body], self.mesh.shape["data"]):
                out[dim_idx_in_body] = fa
            return tuple(out)

        hd = cfg.resolved_head_dim if cfg.n_heads else 0

        if name in ("wq", "wo"):
            tp_ok = self.attn_head_sharded
            if name == "wq":
                spec = (None, "model") if tp_ok else (None, None)
                spec = fs(0, spec)
            else:
                spec = ("model", None) if tp_ok else (None, None)
                spec = fs(1, spec)
        elif name in ("wk", "wv"):
            tp_ok = self.kv_head_sharded
            spec = (None, "model") if tp_ok else (None, None)
            spec = fs(0, spec)
        elif name in ("bq",):
            spec = ("model",) if self.attn_head_sharded else (None,)
        elif name in ("bk", "bv"):
            spec = ("model",) if self.kv_head_sharded else (None,)
        elif name in ("w_up", "w_gate") and len(body) == 2:
            spec = fs(0, (None, "model"))
        elif name == "w_down" and len(body) == 2:
            spec = fs(1, ("model", None))
        elif name in ("w_up", "w_gate", "w_down") and len(body) == 3:
            # MoE expert weights [E, d, f] / [E, f, d]
            if self.expert_parallel:
                spec = fs(1, ("model", None, None))
            else:
                tp_dim = 2 if name in ("w_up", "w_gate") else 1
                spec = [None, None, None]
                spec[tp_dim] = "model"
                spec = fs(1 if tp_dim == 2 else 2, tuple(spec))
        elif name == "router":
            spec = fs(0, (None, None))
        elif name in ("shared_up", "shared_gate"):
            spec = fs(0, (None, "model"))
        elif name == "shared_down":
            spec = fs(1, ("model", None))
        elif name == "table":
            # embedding [V, D]: vocab over model AND data (2-axis shard);
            # keeping D replicated avoids batch-gathering reshards in the
            # embedding-scatter backward.
            if _divisible(body[0], ms * self.mesh.shape.get("data", 1)):
                spec = (("model", "data"), None)
            elif _divisible(body[0], ms):
                spec = (("model",), None)
            else:                        # e.g. hubert's 504-way output
                spec = (None, None)
        elif name == "unembed":          # [D, V]
            if _divisible(body[1], ms * self.mesh.shape.get("data", 1)):
                spec = (None, ("model", "data"))
            elif _divisible(body[1], ms):
                spec = (None, "model")
            else:
                spec = fs(0, (None, None))
        elif name == "frontend_proj":
            spec = fs(0, (None, None))
        # ---- SSD
        elif name == "w_in":
            spec = fs(0, (None, None))   # mixed z|xBC|dt cols: replicate
        elif name == "w_out" and cfg.ssm is not None and \
                _divisible(body[0], ms):
            spec = fs(1, ("model", None))
        # ---- RG-LRU (width divisible -> channel TP)
        elif name in ("w_gate_branch", "w_rec_branch") and \
                _divisible(body[-1], ms):
            spec = fs(0, (None, "model"))
        elif name in ("w_a", "w_x") and _divisible(body[-1], ms):
            spec = fs(0, (None, "model"))
        elif name == "w_out" and cfg.rglru is not None and \
                _divisible(body[0], ms):
            spec = fs(1, ("model", None))
        elif name in ("b_a", "b_x", "Lambda", "conv_b", "norm_scale") and \
                len(body) == 1 and _divisible(body[-1], ms):
            spec = ("model",)
        elif name == "conv_w" and _divisible(body[-1], ms):
            spec = (None, "model")
        else:
            spec = tuple(None for _ in body)
        return P(*(lead + tuple(spec)))

    def param_specs(self, params) -> Dict:
        """Tree of NamedShardings matching the params tree."""
        def rec(tree, stacked: bool, path=()):
            if isinstance(tree, dict):
                return {k: rec(v, stacked or k == "cycles", path + (k,))
                        for k, v in tree.items()}
            if isinstance(tree, (list, tuple)):
                return [rec(v, stacked, path + (str(i),))
                        for i, v in enumerate(tree)]
            name = path[-1]
            return self.ns(*self._param_spec(name, tree.shape, stacked))
        return rec(params, False)

    # ------------------------------------------------------- activations
    def residual(self, x):
        """[B, S, D] — batch over data(+pod), sequence over model (seq par)."""
        if x.ndim != 3:
            return x
        b_ok = _divisible(x.shape[0], self.data_size)
        s_ok = self.seq_parallel and _divisible(x.shape[1], self.model_size) \
            and x.shape[1] > 1
        return self._c(x, self.batch_axes if b_ok else None,
                       "model" if s_ok else None, None)

    def heads(self, q):
        """[B, S, H, hd] — heads over model when divisible, else sequence
        (context parallelism fallback)."""
        if q.ndim != 4:
            return q
        b_ok = _divisible(q.shape[0], self.data_size)
        bspec = self.batch_axes if b_ok else None
        if _divisible(q.shape[2], self.model_size):
            return self._c(q, bspec, None, "model", None)
        if _divisible(q.shape[1], self.model_size) and q.shape[1] > 1:
            return self._c(q, bspec, "model", None, None)
        return self._c(q, bspec, None, None, None)

    def kv(self, k):
        """[B, S, Hkv, hd]. In context-parallel fallback mode the K/V for
        attention must be sequence-replicated — constraining them here (post
        projection + RoPE) makes GSPMD gather the small [B,S,Hkv,hd] heads
        instead of the full residual stream."""
        if k.ndim != 4:
            return k
        b_ok = _divisible(k.shape[0], self.data_size)
        bspec = self.batch_axes if b_ok else None
        if _divisible(k.shape[2], self.model_size):
            return self._c(k, bspec, None, "model", None)
        return self._c(k, bspec, None, None, None)

    def ffn(self, h):
        """[B, S, F] — hidden dim over model."""
        if h.ndim != 3 or not _divisible(h.shape[-1], self.model_size):
            return h
        b_ok = _divisible(h.shape[0], self.data_size)
        return self._c(h, self.batch_axes if b_ok else None, None, "model")

    def moe(self, buf):
        """[E, C, D] dispatch buffer — experts over model when EP."""
        if self.expert_parallel and _divisible(buf.shape[0], self.model_size):
            return self._c(buf, "model", None, None)
        return buf

    def logits(self, logits):
        """Sequence-sharded logits (Megatron seq-parallel CE): softmax and
        take_along_axis stay local, no vocab collectives; the unembedding
        columns are gathered once instead."""
        if logits.ndim != 3:
            return logits
        b_ok = _divisible(logits.shape[0], self.data_size)
        bspec = self.batch_axes if b_ok else None
        s_ok = self.seq_parallel and _divisible(logits.shape[1],
                                                self.model_size) \
            and logits.shape[1] > 1
        if s_ok:
            return self._c(logits, bspec, "model", None)
        if _divisible(logits.shape[-1], self.model_size):
            return self._c(logits, bspec, None, "model")
        return self._c(logits, bspec, None, None)

    # ------------------------------------------------------------ inputs
    def batch_spec(self, shape: Tuple[int, ...]) -> NamedSharding:
        """Tokens/labels/features: shard leading batch dim when divisible."""
        b_ok = _divisible(shape[0], self.data_size)
        return self.ns(self.batch_axes if b_ok else None,
                       *(None,) * (len(shape) - 1))

    def cache_specs(self, cache) -> Dict:
        """KV/ring/SSM caches. [B, L, Hkv, hd]: batch over data, kv-heads
        over model when divisible, else cache length over model."""
        def leaf(path, a):
            name = path[-1]
            lead = (None,) if path_has_cycles(path) else ()
            body = a.shape[1:] if path_has_cycles(path) else a.shape
            bspec = self.batch_axes if _divisible(body[0], self.data_size) \
                else None
            if name in ("k", "v"):
                if _divisible(body[2], self.model_size):
                    spec = (bspec, None, "model", None)
                elif _divisible(body[1], self.model_size):
                    spec = (bspec, "model", None, None)
                else:
                    spec = (bspec, None, None, None)
            elif name == "state":      # [B, H, P, N]
                if _divisible(body[2], self.model_size):
                    spec = (bspec, None, "model", None)
                else:
                    spec = (bspec, None, None, None)
            elif name == "conv":       # [B, W-1, Ch]
                if _divisible(body[2], self.model_size):
                    spec = (bspec, None, "model")
                else:
                    spec = (bspec, None, None)
            elif name == "h":          # [B, W]
                if _divisible(body[1], self.model_size):
                    spec = (bspec, "model")
                else:
                    spec = (bspec, None)
            else:
                spec = tuple(None for _ in body)
            return self.ns(*(lead + tuple(spec)))

        def path_has_cycles(path):
            return "cycles" in path

        def rec(tree, path=()):
            if isinstance(tree, dict):
                return {k: rec(v, path + (k,)) for k, v in tree.items()}
            if isinstance(tree, (list, tuple)):
                return [rec(v, path + (str(i),)) for i, v in enumerate(tree)]
            return leaf(path, tree)
        return rec(cache)

    def report(self) -> str:
        cfg = self.cfg
        lines = [f"policy for {cfg.name} on mesh {dict(self.mesh.shape)}:"]
        lines.append(f"  attention: {'head-TP' if self.attn_head_sharded else 'context-parallel fallback (heads % model != 0)'}")
        if cfg.moe is not None:
            lines.append(f"  moe: {'expert-parallel' if self.expert_parallel else 'TP-within-expert'}")
        lines.append(f"  seq_parallel={self.seq_parallel} fsdp={self.fsdp_axis}")
        return "\n".join(lines)
