"""Device milliseconds per step of the gated attention kernels
(``kernels/d2ft_attention.py``, named ``d2ft_attn_{fwd,bwd}_{short,flash}``),
averaged over the chips; nothing where the trace holds none."""
PREFIX = "d2ft_attn_"


def read(ctx):
    ms = sum(v for n, v in ctx["kernel_ms"].items() if n.startswith(PREFIX))
    return ms if ms > 0 else None
