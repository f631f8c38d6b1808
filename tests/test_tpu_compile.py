"""Compile the Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler, installed beside JAX, compiles each kernel
(``interpret=False``) for a chip it is told about, and raises what the chip
would refuse — block shapes off the (8, 128) tiling, unsupported
primitives, more VMEM than a kernel may use. Each compile takes a second
or two. The topology is described inside a fixture, never at import: only
one process may load the TPU library, and the suite runs on several
workers.

Kernels that need a redesign rather than a layout fix carry their
compiler's message in an ``xfail`` (ROADMAP.md, Speed 2).
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables for a described chip cannot be read back from the
    # persistent cache without the chip: keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shapes(sharding, *shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]


def _compile(fn, *args):
    """Compiled HLO text; asserts the kernel lowered to Mosaic."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _grad(fn, n_diff: int):
    """d(sum fn)/d(first n_diff args)."""
    def g(*args):
        return jax.grad(lambda *d: fn(*d, *args[n_diff:]).sum(),
                        argnums=tuple(range(n_diff)))(*args[:n_diff])
    return g


# (B, H, S, hd, causal, live_fwd, live_bwd, path): ViT-S/16 (6 heads of 64,
# 197 tokens — one unpadded 197-row tile per slice, the short path), the
# benchmark cell's exact dispatch (batch 200, 3 p_f + 1 p_o + 1 p_s of 5
# micro-batches: 960 and 720 live slices of 1200), and stablelm-3b (32
# heads of 80, causal, S=512 — 128x128 tiles, the flash path)
ATTN_SHAPES = {"vit_s16": (8, 6, 197, 64, False, None, None, "short"),
               "vit_s16_cell": (200, 6, 197, 64, False, 960, 720, "short"),
               "stablelm_3b": (2, 32, 512, 80, True, None, None, "flash")}


@pytest.mark.parametrize("name", sorted(ATTN_SHAPES))
@pytest.mark.parametrize("pass_", ["fwd", "grad"])
def test_gated_attention_compiles(one_chip, name, pass_):
    B, H, S, hd, causal, live_fwd, live_bwd, path = ATTN_SHAPES[name]

    def attn(q, k, v, gf, gb):
        return ops.gated_attention(q, k, v, gf, gb, causal=causal,
                                   interpret=False, live_fwd=live_fwd,
                                   live_bwd=live_bwd)

    fn = attn if pass_ == "fwd" else _grad(attn, 3)
    hlo = _compile(fn, *_shapes(one_chip, *[(B, H, S, hd)] * 3, (B, H),
                                (B, H)))
    # the kernels' pallas_call names say which path lowered
    other = {"short": "flash", "flash": "short"}[path]
    for kind in ("fwd",) if pass_ == "fwd" else ("fwd", "bwd"):
        assert f"d2ft_attn_{kind}_{path}" in hlo
        assert f"d2ft_attn_{kind}_{other}" not in hlo


@pytest.mark.parametrize("pass_", ["fwd", "grad"])
def test_gated_ssd_compiles(one_chip, pass_):
    """mamba2-130m: d_inner 1536 = 24 heads of 64, state 128, chunk 256."""
    B, S, H, P, N = 2, 512, 24, 64, 128

    def ssd(x, da, bm, cm, gf, gb):
        return ops.gated_ssd_scan(x, da, bm, cm, gf, gb, chunk=256,
                                  interpret=False)

    fn = ssd if pass_ == "fwd" else _grad(ssd, 4)
    _compile(fn, *_shapes(one_chip, (B, S, H, P), (B, S, H), (B, S, N),
                          (B, S, N), (B, H), (B, H)))


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: 'Unimplemented primitive in Pallas TPU lowering for "
    "KernelType.TC: cumsum' — and the chunk's [Q, Q, W/G] decay tensor "
    "(16 MiB at recurrentgemma-2b widths) needs a sequential redesign"))
@pytest.mark.parametrize("pass_", ["fwd", "grad"])
def test_gated_rglru_compiles(one_chip, pass_):
    """recurrentgemma-2b: lru_width 2560 in 10 gate groups, chunk 128."""
    B, S, W, G = 2, 512, 2560, 10

    def rglru(la, b, gf, gb):
        return ops.gated_rglru_scan(la, b, gf, gb, chunk=128,
                                    interpret=False)

    fn = rglru if pass_ == "fwd" else _grad(rglru, 2)
    _compile(fn, *_shapes(one_chip, (B, S, W), (B, S, W), (B, G), (B, G)))


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: 'RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem "
    "... Scoped allocation with size 52.00M and limit 16.00M' — whole "
    "[D, F] expert weights per grid step; needs tiling over F"))
@pytest.mark.parametrize("pass_", ["fwd", "grad"])
def test_gated_moe_compiles(one_chip, pass_):
    """olmoe-1b-7b: 64 experts, d_model 2048, expert d_ff 1024; capacity of
    2 x 512 tokens at top-8, capacity factor 1.25."""
    E, D, F = 64, 2048, 1024
    C = round(2 * 512 * 8 / E * 1.25)

    def moe(xb, wu, wg, wd, fs, bs):
        return ops.gated_moe_ffn(xb, wu, wg, wd, fs, bs, interpret=False)

    fn = moe if pass_ == "fwd" else _grad(moe, 4)
    _compile(fn, *_shapes(one_chip, (E, C, D), (E, D, F), (E, D, F),
                          (E, F, D), (E, C), (E, C)))


@pytest.mark.xfail(strict=True, reason=(
    "Pallas TPU lowering: block (1, page_size, 1, hd) over the "
    "[n_pages, page_size, n_kv, hd] pool has second-minor dim 1, not "
    "n_kv — the page pool needs a [n_pages, n_kv, page_size, hd] layout"))
def test_paged_decode_compiles(one_chip):
    """stablelm-3b serving: 32 heads of 80, 16-token pages, 8 slots of up
    to 512 tokens."""
    B, H, hd, ps, npm = 8, 32, 80, 16, 32
    n_pages = B * npm + 1
    args = _shapes(one_chip, (B, H, hd), (n_pages, ps, H, hd),
                   (n_pages, ps, H, hd))
    args += _shapes(one_chip, (B, npm), (B,), dtype=jnp.int32)
    _compile(lambda q, kp, vp, t, ln: ops.paged_decode_attention(
        q, kp, vp, t, ln, interpret=False), *args)


def test_lora_linear_compiles(one_chip):
    """stablelm-3b q/k/v projection 2560 -> 3 x 2560 at rank 8, 1024
    tokens."""
    _compile(lambda x, w, a, b: ops.lora_linear(x, w, a, b, 2.0,
                                                interpret=False),
             *_shapes(one_chip, (1024, 2560), (2560, 7680), (2560, 8),
                      (8, 7680)))
