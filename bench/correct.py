"""Whether a run's timed path computed the right thing.

The program's first three steps (the warm-up's, through the same entry
point, feed and compiled step as the window) are held against the plain
reference beside the configuration, run after the window on the same
three batches, the same weights and the schedule the run planned, in
float32 at ``highest`` matmul precision (the configurations state float32
at ``highest``, so the program runs so too). The gradient is clipped to a
global norm of 1, the entry points' own default (``clip=1.0`` in
``train/loop.py``), which the benchmark does not change:

* ``loss_gap``: the largest relative gap of the three steps' losses;
* ``grad_gap``: the first gradient as the optimizer got it (worked out
  from its state after step 1: SGD's momentum, AdamW's first moment over
  1 - b1), per leaf: the gap between the program's norm and the
  reference's, over the larger of the reference's norm of that leaf and
  of the median leaf; the worst leaf;
* ``update_gap``: the same for the norm of each leaf's change over the
  three steps, read as step 4 receives the parameters.

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are not counted (none are in these
configurations; the rule is by value, not by name).

The schedule the run planned is held to the budget of the traffic file:
every (layer, group) gets exactly ``n_pf`` p_f and ``n_po`` p_o
micro-batches (``off_budget``). It is also held against the reference's
own scores, with D2FT's default metrics (the paper's final choice): the
weight magnitude of each subnet for the backward, its Fisher information
on each micro-batch of the first batch for the forward, both from the
seed's weights. ``plan_gap`` is the worst shortfall, over (layer, group),
of the planned picks against the best picks under those scores: p_f's
backward scores against the ``n_pf`` best, then p_o's forward scores
against the ``n_po`` best of the micro-batches left, each as a share of
the best. A subnet (layer, group) is the group's slice of each width-
partitionable weight of the layer (the output columns of the query, key,
value and MLP input projections, the input rows of the attention output
and MLP output projections) and the whole of every other weight of the
layer; a reference module that defines ``group_sums(block, G, power)``
splits its own layers instead (a fused weight whose columns belong to
different groups, or to none).
"""
from __future__ import annotations

import numpy as np

P_F, P_O, P_S = 1, 2, 3
DEAD_LEAF = 1e-3
# the control's precision, one step below the configuration's float32 at
# the matmul precision it states: three bf16 passes below six, bfloat16
# below one
LOWER = {"highest": ("HIGH", "float32"), "default": ("DEFAULT", "bfloat16")}


# ------------------------------------------------------------ leaf norms
def _norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                          for x in jax.tree.leaves(tree)])

    @jax.jit
    def diff_norms(a, b):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
    return norms, diff_norms


_FNS = {}


def _fns():
    if not _FNS:
        _FNS["norms"], _FNS["diff"] = _norms_fn()
    return _FNS["norms"], _FNS["diff"]


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(_fns()[0](tree), np.float64)


def change_norms(p, p0) -> np.ndarray:
    return np.asarray(_fns()[1](p, p0), np.float64)


def program_grad_norms(opt_state, o: dict) -> np.ndarray:
    """Per-leaf norms of the first gradient, from the state after step 1."""
    if o["name"] == "sgd":
        return leaf_norms(opt_state["mu"])
    return leaf_norms(opt_state["m"]) / (1.0 - o["b1"])


# ------------------------------------------------------------- reference
def gates(table: np.ndarray, mb_of: np.ndarray):
    """(g_f, g_b) [L, B, G] float32 of a [L, G, N] op table."""
    per = np.asarray(table)[:, :, mb_of].transpose(0, 2, 1)
    return (per != P_S).astype(np.float32), (per == P_F).astype(np.float32)


def _plain_opt(o: dict):
    import jax
    import jax.numpy as jnp
    tm = jax.tree.map

    if o["name"] == "sgd":
        def init(p):
            return {"mu": tm(jnp.zeros_like, p)}

        def update(g, s, p, t):
            mu = tm(lambda m, x: o["momentum"] * m + x, s["mu"], g)
            return tm(lambda a, m: a - o["lr"] * m, p, mu), {"mu": mu}
        return init, update

    b1, b2, eps, wd, lr = o["b1"], o["b2"], o["eps"], o["weight_decay"], \
        o["lr"]

    def init(p):
        return {"m": tm(jnp.zeros_like, p), "v": tm(jnp.zeros_like, p)}

    def update(g, s, p, t):
        m = tm(lambda a, x: b1 * a + (1 - b1) * x, s["m"], g)
        v = tm(lambda a, x: b2 * a + (1 - b2) * x * x, s["v"], g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new = tm(lambda a, mm, vv: a - lr * ((mm / c1) / (jnp.sqrt(vv / c2)
                                                          + eps) + wd * a),
                 p, m, v)
        return new, {"m": m, "v": v}
    return init, update


def reference_steps(ref, c: dict, seed: int, batches, gate_list, *,
                    row_block: int, lowered: bool = False,
                    rows: int | None = None, n_steps: int = 3,
                    score: tuple | None = None) -> dict:
    """Three plain steps from the seed's weights.

    ``lowered``: the control, the same steps in the precision just below
    the configuration's (``LOWER``); the master weights and the optimizer
    stay float32. ``rows``: use only the first ``rows`` samples of each
    batch (the half-batch fault). ``score``: (groups, micro-batches), to
    score the subnets first (module docstring)."""
    import jax
    import jax.numpy as jnp
    from bench.harness import key_of

    prec, dt = jax.lax.Precision.HIGHEST, jnp.float32
    if lowered:
        name, dtype = LOWER[c["precision"]["matmul"]]
        prec, dt = jax.lax.Precision[name], jnp.dtype(dtype)

    def block_loss(p, batch, gf, gb):
        p = jax.tree.map(lambda a: a.astype(dt), p)
        return ref.loss(c, p, batch, gf, gb, prec).astype(jnp.float32)

    # the gradient, weights and moments are updated in place: at LLM widths
    # a second copy of them does not fit beside the first
    vg = jax.jit(jax.value_and_grad(block_loss))
    acc = jax.jit(lambda a, g, w: jax.tree.map(lambda x, y: x + w * y, a, g),
                  donate_argnums=0)
    clip = jax.jit(_clip_fn(1.0), donate_argnums=0)
    init, update = _plain_opt(c["optimizer"])
    update = jax.jit(update, donate_argnums=(1, 2))
    weights = jax.jit(lambda k: ref.init(c, k))
    params = weights(key_of(seed))
    state = jax.jit(init)(params)
    scores = None
    if score is not None:
        scores = subnet_scores(ref, vg, params, batches[0], gate_list[0],
                               *score)
    losses, grad_norms = [], None
    for step in range(n_steps):
        batch, (gf, gb) = batches[step], gate_list[step]
        B = rows or ref.rows(batch)
        loss, grads = 0.0, None
        for lo in range(0, B, row_block):
            hi = min(B, lo + row_block)
            l, g = vg(params, ref.take(batch, lo, hi),
                      jnp.asarray(gf[:, lo:hi]), jnp.asarray(gb[:, lo:hi]))
            w = (hi - lo) / B
            loss += float(l) * w
            grads = jax.tree.map(lambda x: x * w, g) if grads is None \
                else acc(grads, g, w)
        grads = clip(grads)
        if step == 0:
            grad_norms = leaf_norms(grads)
        params, state = update(grads, state, params, step + 1)
        losses.append(loss)
    return {"losses": losses, "grad_norms": grad_norms, "scores": scores,
            "change_norms": change_norms(params, weights(key_of(seed)))}


# ----------------------------------------------------------------- scores
COLUMNS = ("wq", "wk", "wv", "w_up", "w_gate")   # sliced by output column
ROWS = ("wo", "w_down")                           # sliced by input row


def group_sums(block, G: int, power: int):
    """[G]: the sum of |x| ** power over each group's subnet of one layer's
    weights ``block`` (module docstring)."""
    import jax
    import jax.numpy as jnp
    tot = jnp.zeros((G,), jnp.float32)
    for path, x in jax.tree_util.tree_flatten_with_path(block)[0]:
        name = path[-1].key
        v = jnp.abs(x.astype(jnp.float32)) ** power
        if name in COLUMNS and x.shape[-1] % G == 0:
            tot = tot + v.reshape(-1, G, x.shape[-1] // G).sum((0, 2))
        elif name in ROWS and x.shape[0] % G == 0:
            tot = tot + v.reshape(G, -1).sum(1)
        else:
            tot = tot + jnp.sum(v)
    return tot


def _subnet_sums(ref, G: int, power: int):
    """[L, G]: per layer and group, the sum of |x| ** power over the
    group's subnet, by the reference's own ``group_sums`` where it has
    one."""
    import jax
    import jax.numpy as jnp
    split = getattr(ref, "group_sums", group_sums)

    @jax.jit
    def sums(blocks):
        return jnp.stack([split(blk, G, power) for blk in blocks])
    return sums


def subnet_scores(ref, vg, params, batch, gates, G: int, M: int) -> tuple:
    """(backward [L, G], forward [L, G, M]): each subnet's weight magnitude,
    and its Fisher information (summed squared gradient of the mean loss)
    on each contiguous micro-batch of ``batch``, every gate open."""
    import jax.numpy as jnp
    back = np.asarray(_subnet_sums(ref, G, 1)(ref.blocks(params)),
                      np.float64)
    fisher = _subnet_sums(ref, G, 2)
    B = ref.rows(batch)
    b = B // M
    ones = jnp.ones((gates[0].shape[0], b, gates[0].shape[2]), jnp.float32)
    fwd = np.stack([np.asarray(fisher(ref.blocks(
        vg(params, ref.take(batch, m * b, (m + 1) * b), ones, ones)[1])),
        np.float64) for m in range(M)], axis=-1)
    return back, fwd


def plan_gap(table: np.ndarray, scores: tuple, t: dict) -> float:
    """Worst shortfall of a [L, G, M] op table's picks against the best
    picks under the reference's ``scores`` (module docstring)."""
    back, fwd = scores
    back = np.broadcast_to(back[..., None], fwd.shape)
    table = np.asarray(table)
    pf, po = table == P_F, table == P_O

    def shortfall(score, chosen, k, allowed):
        if k == 0:
            return np.zeros(score.shape[:-1])
        best = -np.sort(-np.where(allowed, score, -np.inf), -1)[..., :k]
        best = best.sum(-1)
        return (best - np.where(chosen, score, 0.0).sum(-1)) / best

    return float(max(
        np.max(shortfall(back, pf, t["n_pf"], np.ones_like(pf))),
        np.max(shortfall(fwd, po, t["n_po"], ~pf))))


def misplan(table: np.ndarray) -> np.ndarray:
    """A planning fault with the budget kept: in every (layer, group), the
    p_o micro-batch and the first p_s one trade places."""
    out = np.array(table)
    for row in out.reshape(-1, out.shape[-1]):
        o, s = np.flatnonzero(row == P_O), np.flatnonzero(row == P_S)
        if len(o) and len(s):
            row[o[0]], row[s[0]] = P_S, P_O
    return out


def _clip_fn(max_norm: float):
    import jax
    import jax.numpy as jnp

    def clip(g):
        n = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        s = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
        return jax.tree.map(lambda x: x * s, g)
    return clip


# ------------------------------------------------------------ comparison
def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers (module docstring)."""
    lp, lr = np.asarray(prog["losses"][:3]), np.asarray(ref["losses"][:3])
    gr, cr = ref["grad_norms"], ref["change_norms"]
    keep = gr >= DEAD_LEAF * np.median(gr)
    g_med, c_med = np.median(gr), np.median(cr)
    gp, cp = prog["grad_norms"], prog["change_norms"]
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": float(np.max((np.abs(gp - gr) / np.maximum(gr, g_med))
                                 [keep])),
        "update_gap": float(np.max((np.abs(cp - cr) / np.maximum(cr, c_med))
                                   [keep])),
    }


def off_budget(table: np.ndarray, t: dict) -> int:
    """(layer, group) rows whose op counts miss the traffic's budget."""
    rows = np.asarray(table).reshape(-1, table.shape[-1])
    ok = (np.sum(rows == P_F, 1) == t["n_pf"]) & \
        (np.sum(rows == P_O, 1) == t["n_po"])
    return int(np.sum(~ok))


def reference_inputs(run):
    """The first three batches and their gates, as the reference takes
    them: the whole batch, micro-batches split in order."""
    from bench.flops import full_table
    from bench.harness import microbatch_of
    c, t = run.config, run.traffic
    table = run.table if run.table is not None else full_table(
        c["num_hidden_layers"], c["num_attention_heads"], t["n_microbatches"])
    g = gates(table, microbatch_of(t["batch"], t["n_microbatches"]))
    return run.feed.pool[:3], [g] * 3


def check(layout, run) -> dict:
    """The numbers compared, each with its limit."""
    c, t = run.config, run.traffic
    _, ref = layout.config(run.cell["config"])
    batches, gate_list = reference_inputs(run)
    d2ft = t.get("d2ft", True)
    refr = reference_steps(
        ref, c, run.seed, batches, gate_list, row_block=t["ref_rows"],
        score=(run.table.shape[1], t["n_microbatches"]) if d2ft else None)
    run.reference = refr
    prog = {"losses": run.losses[:3], **run.captured}
    out = {}
    limits = t["limits"]
    for name, v in gaps(prog, refr).items():
        out[name] = {"value": v, "limit": limits[name]}
    if d2ft:
        out["off_budget"] = {"value": off_budget(run.table, t), "limit": 0}
        out["plan_gap"] = {"value": plan_gap(run.table, refr["scores"], t),
                           "limit": limits["plan_gap"]}
    return out
