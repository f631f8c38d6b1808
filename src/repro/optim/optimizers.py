"""Minimal pure-JAX optimizer library (no optax dependency).

The paper fine-tunes with SGD + momentum (§IV-A); AdamW is provided for the
LLM fine-tuning paths. Optimizer states are pytrees shaped like params and
``update`` is leaf-wise, so the same Optimizer runs replicated, on ZeRO-1
moment shards, or fully shard-resident under ZeRO-3 (grads, moments and
params all at shard shape — sharding/sync.py / train/loop.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], Tuple[Any, Any]]  # (grads, state, params)
    # True when a parameter shard with zero gradient AND zero moments gets
    # an exactly-identity update (no weight decay): the ZeRO-1 sync may
    # then elide the param all-gather for runs that have been backward-dead
    # since their moments were last zero (sharding/sync.py zero mode).
    # ZeRO-3 does NOT need this: its owned shards are always updated (decay
    # included) and its gather elision is a forward-liveness question only.
    elidable: bool = True
    # params-shaped moment copies in the state (sgd: mu; adamw: m and v) —
    # what the ZeRO memory accounting (zero_state_byte_report) multiplies
    # by, instead of every call site hard-coding the optimizer family.
    n_moments: int = 1


def sgd(lr: float, momentum: float = 0.9, weight_decay: float = 0.0,
        nesterov: bool = False) -> Optimizer:
    def init(params):
        return {"mu": jax.tree.map(jnp.zeros_like, params),
                "step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        if weight_decay:
            grads = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
        mu = jax.tree.map(lambda m, g: momentum * m + g, state["mu"], grads)
        if nesterov:
            upd = jax.tree.map(lambda m, g: momentum * m + g, mu, grads)
        else:
            upd = mu
        new_params = jax.tree.map(lambda p, u: p - lr * u, params, upd)
        return new_params, {"mu": mu, "step": state["step"] + 1}

    return Optimizer(init, update, elidable=weight_decay == 0.0,
                     n_moments=1)


def adamw(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.01) -> Optimizer:
    def init(params):
        return {"m": jax.tree.map(jnp.zeros_like, params),
                "v": jax.tree.map(jnp.zeros_like, params),
                "step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        step = state["step"] + 1
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, m_, v_):
            mh = m_ / bc1
            vh = v_ / bc2
            return p - lr * (mh / (jnp.sqrt(vh) + eps) + weight_decay * p)

        new_params = jax.tree.map(upd, params, m, v)
        return new_params, {"m": m, "v": v, "step": step}

    return Optimizer(init, update, elidable=weight_decay == 0.0,
                     n_moments=2)


def chunked(opt: Optimizer, chunk: int) -> Optimizer:
    """Stream ``opt``'s update chunk-by-chunk (ChunkFT style).

    The update of every optimizer here is leafwise *and* elementwise, so
    each leaf (and its params-shaped moment entries) can be flattened,
    zero-padded to a chunk multiple and updated one ``chunk``-sized slice
    at a time under ``jax.lax.map`` — the live working set of the update
    is O(chunk) instead of O(leaf), which is what makes the ZeRO-3
    shard-resident update byte-streamable. Every chunk sees the *same*
    input ``step`` (bias correction matches the whole-shard update) and
    the step counter advances once per call, so results match
    ``opt.update`` to a few ulp of each leaf's scale, not bit for bit: XLA
    fuses the elementwise update differently under ``lax.map`` (a fused
    multiply-add or not), which moves a result by one rounding. A
    hypothesis property pins 4 ulp for sgd and adamw
    (tests/test_sync_properties.py). Zero padding is benign: an
    elementwise update of (g=0, p=0, m=0) is 0 and the padded tail is
    discarded anyway.
    """
    assert chunk >= 1, chunk

    def update(grads, state, params):
        moment_keys = [k for k in state if k != "step"]
        step = state["step"]
        g_leaves, treedef = jax.tree.flatten(grads)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = {k: treedef.flatten_up_to(state[k]) for k in moment_keys}
        new_p, new_m = [], {k: [] for k in moment_keys}
        for i, (g, p) in enumerate(zip(g_leaves, p_leaves)):
            n, shape = g.size, g.shape
            pad = (-n) % chunk

            def flat(x):
                return jnp.pad(x.reshape(-1), (0, pad)).reshape(-1, chunk)

            def body(sl):
                g_c, p_c, *m_c = sl
                st = {"step": step, **dict(zip(moment_keys, m_c))}
                p_new, st_new = opt.update(g_c, st, p_c)
                return (p_new, *[st_new[k] for k in moment_keys])

            out = jax.lax.map(body, (flat(g), flat(p),
                                     *[flat(m_leaves[k][i])
                                       for k in moment_keys]))

            def unflat(x):
                return x.reshape(-1)[:n].reshape(shape)

            new_p.append(unflat(out[0]))
            for k, m in zip(moment_keys, out[1:]):
                new_m[k].append(unflat(m))
        new_state = {k: jax.tree.unflatten(treedef, new_m[k])
                     for k in moment_keys}
        new_state["step"] = step + 1
        return jax.tree.unflatten(treedef, new_p), new_state

    return Optimizer(opt.init, update, elidable=opt.elidable,
                     n_moments=opt.n_moments)


def clip_scale(norm, max_norm: float):
    """Global-norm clip factor — shared by clip_by_global_norm and the
    distributed ZeRO step (which computes the norm itself, via a scalar
    psum over grad shards)."""
    return jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
    scale = clip_scale(norm, max_norm)
    return jax.tree.map(lambda g: g * scale, grads), norm
