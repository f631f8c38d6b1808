"""Plain reference of ViT-S/16 under a D2FT schedule (see vit-s16.json).

Patch embedding, a CLS token and learned positions, 12 pre-norm blocks of
bidirectional softmax attention and a GELU (tanh) MLP, a final LayerNorm
and a linear classifier on the CLS token; mean cross-entropy. ``init``
makes the weights from a key: the benchmark hands the same weights to the
program and to this reference.
"""
import jax
import jax.numpy as jnp

from bench import refmath as rm


def init(c, key):
    D, F = c["hidden_size"], c["intermediate_size"]
    L, C = c["num_hidden_layers"], c["num_labels"]
    pd = c["patch_size"] ** 2 * c["num_channels"]
    n = (c["image_size"] // c["patch_size"]) ** 2
    f32 = jnp.float32
    ks = jax.random.split(key, L + 4)

    def block(k):
        kq, kk, kv, ko, ku, kd = jax.random.split(k, 6)
        ln = {"scale": jnp.ones((D,), f32), "bias": jnp.zeros((D,), f32)}
        return {"norm1": ln,
                "attn": {"wq": rm.dense(kq, D, D, f32),
                         "wk": rm.dense(kk, D, D, f32),
                         "wv": rm.dense(kv, D, D, f32),
                         "wo": rm.dense(ko, D, D, f32)},
                "norm2": dict(ln),
                "mlp": {"w_up": rm.dense(ku, D, F, f32),
                        "w_down": rm.dense(kd, F, D, f32)}}

    return {"patch_proj": rm.dense(ks[0], pd, D, f32),
            "patch_bias": jnp.zeros((D,), f32),
            "cls": rm.normal(ks[1], (1, 1, D), 0.02, f32),
            "pos": rm.normal(ks[2], (1, n + 1, D), 0.02, f32),
            "blocks": [block(ks[3 + i]) for i in range(L)],
            "final_norm": {"scale": jnp.ones((D,), f32),
                           "bias": jnp.zeros((D,), f32)},
            "head": rm.dense(ks[3 + L], D, C, f32)}


def blocks(params):
    """The per-layer weights, one dict a layer."""
    return list(params["blocks"])


def rows(batch):
    return batch[0].shape[0]


def take(batch, lo, hi):
    return batch[0][lo:hi], batch[1][lo:hi]


def loss(c, params, batch, gf, gb, precision):
    """Mean cross-entropy; gf/gb [L, B, G] are the forward and backward
    gates. Computes in the dtype of ``params``."""
    images, labels = batch
    dt = params["head"].dtype
    D, H, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    p, eps = c["patch_size"], c["layer_norm_eps"]
    B, Hi, Wi, Ch = images.shape
    x = images.astype(dt).reshape(B, Hi // p, p, Wi // p, p, Ch)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, -1, p * p * Ch)
    x = rm.mm(x, params["patch_proj"], precision) + params["patch_bias"]
    cls = jnp.broadcast_to(params["cls"], (B, 1, D))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"]
    S = x.shape[1]
    for l, blk in enumerate(params["blocks"]):
        live, dead = rm.gate_split(gf[l].astype(dt), gb[l].astype(dt))
        h = rm.layer_norm(blk["norm1"], x, eps)
        a = blk["attn"]
        q, k, v = (rm.mm(h, a[w], precision).reshape(B, S, H, hd)
                   for w in ("wq", "wk", "wv"))
        o = rm.attention(q, k, v, False, precision).reshape(B, S, H * hd)
        x = x + rm.gated_project(o, a["wo"], live, dead, precision)
        h = rm.layer_norm(blk["norm2"], x, eps)
        hid = jax.nn.gelu(rm.mm(h, blk["mlp"]["w_up"], precision),
                          approximate=True)
        x = x + rm.gated_project(hid, blk["mlp"]["w_down"], live, dead,
                                 precision)
    x = rm.layer_norm(params["final_norm"], x, eps)
    logits = rm.mm(x[:, 0], params["head"], precision)
    return rm.mean_xent(logits, labels)
