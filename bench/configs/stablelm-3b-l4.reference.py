"""Plain reference of stablelm-3b (depth cut to 4) under a D2FT schedule.

Token embedding, pre-norm blocks of causal multi-head attention with
rotary positions over the whole head and a gated SiLU MLP, LayerNorm,
an untied unembedding and mean next-token cross-entropy (see
stablelm-3b-l4.json for the departures from the published model).
``init`` makes the weights from a key, the layers stacked on a leading
axis (the layout the program's scanned layers take): the benchmark hands
the same weights to the program and to this reference.
"""
import jax
import jax.numpy as jnp

from bench import refmath as rm


def init(c, key):
    D, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    L, H, hd = c["num_hidden_layers"], c["num_attention_heads"], c["head_dim"]
    Hkv = c["num_key_value_heads"]
    f32 = jnp.float32
    ke, ku, kl = jax.random.split(key, 3)

    def block(k):
        kq, kk, kv, ko, kg, kup, kd = jax.random.split(k, 7)
        ln = {"scale": jnp.ones((D,), f32), "bias": jnp.zeros((D,), f32)}
        return {"norm1": ln,
                "attn": {"wq": rm.dense(kq, D, H * hd, f32),
                         "wk": rm.dense(kk, D, Hkv * hd, f32),
                         "wv": rm.dense(kv, D, Hkv * hd, f32),
                         "wo": rm.dense(ko, H * hd, D, f32)},
                "norm2": dict(ln),
                "mlp": {"w_up": rm.dense(kup, D, F, f32),
                        "w_down": rm.dense(kd, F, D, f32),
                        "w_gate": rm.dense(kg, D, F, f32)}}

    stacked = jax.vmap(block)(jax.random.split(kl, L))
    return {"embed": {"table": rm.normal(ke, (V, D), 0.02, f32)},
            "final_norm": {"scale": jnp.ones((D,), f32),
                           "bias": jnp.zeros((D,), f32)},
            "unembed": rm.dense(ku, D, V, f32),
            "cycles": [stacked], "rest": []}


def blocks(params):
    """The per-layer weights, one dict a layer."""
    stacked = params["cycles"][0]
    n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda a: a[l], stacked) for l in range(n)]


def rows(batch):
    return batch["tokens"].shape[0]


def take(batch, lo, hi):
    return {k: v[lo:hi] for k, v in batch.items()}


def loss(c, params, batch, gf, gb, precision):
    """Mean next-token cross-entropy; gf/gb [L, B, G] are the forward and
    backward gates. Computes in the dtype of ``params``."""
    tokens, labels = batch["tokens"], batch["labels"]
    dt = params["unembed"].dtype
    H, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, theta = c["layer_norm_eps"], c["rope_theta"]
    B, S = tokens.shape
    x = params["embed"]["table"][tokens]
    layers = params["cycles"][0]
    for l in range(c["num_hidden_layers"]):
        blk = jax.tree.map(lambda a: a[l], layers)
        live, dead = rm.gate_split(gf[l].astype(dt), gb[l].astype(dt))
        h = rm.layer_norm(blk["norm1"], x, eps)
        a = blk["attn"]
        q = rm.mm(h, a["wq"], precision).reshape(B, S, H, hd)
        k = rm.mm(h, a["wk"], precision).reshape(B, S, Hkv, hd)
        v = rm.mm(h, a["wv"], precision).reshape(B, S, Hkv, hd)
        q, k = rm.rope(q, theta), rm.rope(k, theta)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        o = rm.attention(q, k, v, True, precision).reshape(B, S, H * hd)
        x = x + rm.gated_project(o, a["wo"], live, dead, precision)
        h = rm.layer_norm(blk["norm2"], x, eps)
        m = blk["mlp"]
        hid = jax.nn.silu(rm.mm(h, m["w_gate"], precision)) \
            * rm.mm(h, m["w_up"], precision)
        x = x + rm.gated_project(hid, m["w_down"], live, dead, precision)
    x = rm.layer_norm(params["final_norm"], x, eps)
    logits = rm.mm(x, params["unembed"], precision)
    return rm.mean_xent(logits, labels)
