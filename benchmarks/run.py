"""Paper-table benchmarks. One function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time per
fine-tune step where applicable). Datasets are synthetic (offline
container); the deliverable is the ORDERING/BUDGET structure of each paper
table, not absolute CIFAR numbers. On-chip numbers live in ``bench/``
(``BENCHMARK.json``, ``PERF.md``), not here.

  python -m benchmarks.run            # all tables
  python -m benchmarks.run --only workload_variance,po_sweep
  python -m benchmarks.run --list     # available entry names
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import D2FTConfig
from repro.core.cost_model import comm_cost, compute_cost, workload_variance
from repro.core.knapsack import scalarized_select
from repro.core.schedule import Schedule, merge_tables
from repro.launch.compile_cache import enable_compile_cache
from benchmarks import common
from benchmarks.common import (VIT, N_MB, d2ft_schedule_fn,
                               dpruning_schedule_fn, emit, gshard_schedule_fn,
                               random_schedule_fn, run_finetune, vit_scores)


# ------------------------------------------------------- Table I + Table II
def bench_workload_variance():
    """Paper Table I (+ execution time, Table II) at the 60% compute budget
    (3 p_f of 5 micro-batches)."""
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=3, n_po=0)
    params = common.pretrained_vit()
    task = common.downstream_task()
    images, labels = next(common.image_batches(task, 5, common.BATCH, 1))

    rows = {}
    sched = d2ft_schedule_fn(d2)(0, params, images, labels)
    rows["D2FT"] = sched.table
    rows["Random"] = random_schedule_fn(d2)(0, params, images, labels).table
    rows["DPruning_M"] = dpruning_schedule_fn(0.6)(0, params, images,
                                                   labels).table
    rows["DPruning_MG"] = dpruning_schedule_fn(0.6, "mg")(0, params, images,
                                                          labels).table
    rows["MoE_GShard"] = gshard_schedule_fn(capacity=3)(0, params, images,
                                                        labels).table
    for name, table in rows.items():
        emit(f"table1_variance_{name}", 0.0,
             f"variance={workload_variance(table):.3f};"
             f"compute={compute_cost(table):.2f}")


def bench_execution_time():
    """Paper Table II: per-step wall time + accuracy under each scheduler."""
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=3, n_po=0)
    for name, fn in [
            ("D2FT", d2ft_schedule_fn(d2)),
            ("Random", random_schedule_fn(d2)),
            ("DPruning_M", dpruning_schedule_fn(0.6)),
            ("DPruning_MG", dpruning_schedule_fn(0.6, "mg")),
            ("MoE_GShard", gshard_schedule_fn(capacity=3))]:
        acc, per_step, _ = run_finetune(fn)
        emit(f"table2_exec_{name}", per_step * 1e6, f"top1={acc:.3f}")


# ------------------------------------------------------------- Fig. 1 and 2
def bench_accuracy_vs_cost():
    """Fig. 1/2: top-1 at matched compute budgets for all methods."""
    acc_std, per_step, _ = run_finetune(None)
    emit("fig12_Standard", per_step * 1e6, "top1=%.3f;compute=1.00" % acc_std)
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=3, n_po=1)
    for name, fn in [
            ("D2FT", d2ft_schedule_fn(d2)),
            ("Random", random_schedule_fn(d2)),
            ("DPruning_M", dpruning_schedule_fn(0.68)),
            ("MoE_GShard", gshard_schedule_fn(capacity=3))]:
        acc, per_step, _ = run_finetune(fn)
        emit(f"fig12_{name}", per_step * 1e6,
             f"top1={acc:.3f};compute=0.68")


# ------------------------------------------------------------------ Table III
def bench_score_combos():
    """Paper Table III: backward/forward score metric combinations."""
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=2, n_po=2)
    combos = [("weight_magnitude", "fisher"),
              ("fisher", "weight_magnitude"),
              ("weight_magnitude", "gradient_magnitude"),
              ("gradient_magnitude", "weight_magnitude"),
              ("weight_magnitude", "taylor"),
              ("taylor", "weight_magnitude")]
    for bw, fw in combos:
        fn = d2ft_schedule_fn(d2, backward=bw, forward=fw)
        acc, per_step, _ = run_finetune(fn)
        emit(f"table3_{bw}__{fw}", per_step * 1e6, f"top1={acc:.3f}")


# ------------------------------------------------------------------ Table IV
def bench_fwd_bwd_ratio():
    """Paper Table IV: forward cost ≈ 40% of fwd+bwd — measured here from
    compiled HLO FLOPs of the ViT instead of wall time."""
    params = common.pretrained_vit()
    task = common.downstream_task()
    images, labels = next(common.image_batches(task, 5, common.BATCH, 1))
    x, y = jnp.asarray(images), jnp.asarray(labels)

    def loss(p):
        from repro.models.vit import vit_loss
        return vit_loss(p, x, y, VIT)[0]

    fwd = jax.jit(loss).lower(params).compile().cost_analysis()
    bwd = jax.jit(jax.value_and_grad(loss)).lower(params).compile() \
        .cost_analysis()
    f, fb = float(fwd.get("flops", 0)), float(bwd.get("flops", 0))
    emit("table4_fwd_fraction", 0.0,
         f"fwd_flops={f:.3e};fwdbwd_flops={fb:.3e};ratio={f/fb:.3f}")


# ------------------------------------------------------------------- Table V
def bench_num_subnets():
    """Paper Table V: more subnets (finer granularity) >= fewer."""
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=2, n_po=2)
    for G in (6, 3, 1):          # 12, 6, 2 subnets on the 2-layer test ViT
        fn = d2ft_schedule_fn(d2, G=G)
        acc, per_step, _ = run_finetune(fn)
        emit(f"table5_subnets_{VIT.n_layers * G}", per_step * 1e6,
             f"top1={acc:.3f}")


# ------------------------------------------------------------------ Table VI
def bench_microbatch_size():
    """Paper Table VI: micro-batch size has minor impact at fixed budget."""
    for n_mb, n_pf, n_po in [(4, 2, 1), (5, 2, 2), (10, 4, 4)]:
        d2 = D2FTConfig(n_microbatches=n_mb, n_pf=n_pf, n_po=n_po)
        fn = d2ft_schedule_fn(d2)
        acc, per_step, _ = run_finetune(fn, n_mb=n_mb)
        emit(f"table6_mb{common.BATCH // n_mb}", per_step * 1e6,
             f"top1={acc:.3f}")


# ----------------------------------------------------------- Tables VII/VIII
def bench_heterogeneous():
    """Paper Tables VII/VIII: per-device capacities (memory/compute
    heterogeneity) do not hurt accuracy."""
    K = VIT.n_layers * VIT.n_heads
    for n_fast in (3, 6, 9):
        cap_pf = np.full(K, 2.0)
        cap_pf[:n_fast] = 3.0          # fast devices: 3 p_f
        cap_po = np.full(K, 0.8)
        cap_po[:n_fast] = 0.4          # fast devices trade p_o for p_f
        d2 = D2FTConfig(n_microbatches=N_MB, n_pf=2, n_po=2)
        fn = d2ft_schedule_fn(d2, cap_pf=cap_pf, cap_po=cap_po)
        acc, per_step, _ = run_finetune(fn)
        emit(f"table78_heterogeneous_fast{n_fast}", per_step * 1e6,
             f"top1={acc:.3f}")


# ------------------------------------------------------------------ Table IX
def bench_po_sweep():
    """Paper Table IX: p_o count is a cheap accuracy lever (1 p_f fixed)."""
    for n_po in range(0, 5):
        d2 = D2FTConfig(n_microbatches=N_MB, n_pf=1, n_po=n_po)
        fn = d2ft_schedule_fn(d2)
        acc, per_step, _ = run_finetune(fn)
        cost = (1.0 + 0.4 * n_po) / N_MB
        emit(f"table9_po{n_po}", per_step * 1e6,
             f"top1={acc:.3f};compute={cost:.2f}")


# ------------------------------------------------------------------- Table X
def bench_bilevel_vs_scaler():
    """Paper Table X: bi-level decoupling vs scalarized single knapsack."""
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=2, n_po=2)
    acc, per_step, _ = run_finetune(d2ft_schedule_fn(d2))
    emit("table10_bilevel", per_step * 1e6, f"top1={acc:.3f}")

    def scaler_fn(lam_mode):
        def fn(step, params, images, labels):
            if step % 16 != 0:
                return None
            bw, fw = vit_scores(params, images, labels)
            if lam_mode == "max":
                lam = 0.99 * bw.min() / max(fw.max(), 1e-9)
            elif lam_mode == "min":
                lam = 1.01 * bw.max() / max(fw.min(), 1e-9)
            else:
                lam = float(lam_mode)
            K = bw.shape[0]
            pf = np.zeros((K, N_MB), bool)
            po = np.zeros((K, N_MB), bool)
            for k in range(K):
                pf[k], po[k] = scalarized_select(bw[k], fw[k], lam, 0.4, 0.6,
                                                 cap_total=2.8)
            return Schedule(merge_tables(pf, po), VIT.n_layers, VIT.n_heads)
        return fn

    for lam in ("max", "min", 0.2, 0.1):
        acc, per_step, _ = run_finetune(scaler_fn(lam))
        emit(f"table10_scaler_{lam}", per_step * 1e6, f"top1={acc:.3f}")


# -------------------------------------------------------------------- Fig. 3
def bench_lora():
    """Fig. 3: D2FT-LoRA vs standard LoRA vs small-rank LoRA at matched
    compute. LoRA on the test ViT's QKV weights."""
    from repro.core.lora import init_lora, merge_lora
    from repro.core.schedule import gates_from_schedule
    from repro.data.synthetic import microbatch_assignment
    from repro.models.vit import vit_loss
    from repro.optim.optimizers import sgd as make_sgd
    from repro.train.loop import eval_vit

    task = common.downstream_task()
    base = common.pretrained_vit()
    d2 = D2FTConfig(n_microbatches=N_MB, n_pf=3, n_po=0)

    def lora_finetune(rank, schedule_fn=None, steps=common.FT_STEPS):
        lora = init_lora(jax.random.PRNGKey(3), base, rank=rank)
        opt = make_sgd(common.LR)
        st = opt.init(lora)
        sched = None

        @jax.jit
        def step_fn(lr, st, x, y, gates):
            def loss(lr):
                merged = merge_lora(base, lr, 1.0)
                return vit_loss(merged, x, y, VIT, gates=gates)[0]
            g = jax.grad(loss)(lr)
            return opt.update(g, st, lr)

        lora_p = lora
        for i, (images, labels) in enumerate(
                common.image_batches(task, 5, common.BATCH, steps)):
            gates = None
            if schedule_fn is not None:
                new = schedule_fn(i, merge_lora(base, lora_p, 1.0),
                                  images, labels)
                sched = new if new is not None else sched
                mb_of = microbatch_assignment(common.BATCH, N_MB)
                gates = gates_from_schedule(sched, mb_of)
            lora_p, st = step_fn(lora_p, st, jnp.asarray(images),
                                 jnp.asarray(labels), gates)
        merged = merge_lora(base, lora_p, 1.0)
        return eval_vit(merged, VIT, common.image_batches(task, 7,
                                                          common.BATCH, 5))

    acc_std = lora_finetune(rank=24)
    emit("fig3_standard_lora_r24", 0.0, f"top1={acc_std:.3f};compute=1.00")
    acc_small = lora_finetune(rank=2)
    emit("fig3_small_rank_r2", 0.0, f"top1={acc_small:.3f};compute=0.60")
    acc_d2ft = lora_finetune(rank=24, schedule_fn=d2ft_schedule_fn(d2))
    emit("fig3_d2ft_lora_r24", 0.0, f"top1={acc_d2ft:.3f};compute=0.60")


# ------------------------------------------- gated kernel backward savings
BENCH_KERNEL_BACKWARD_JSON = "BENCH_kernel_backward.json"


def bench_kernel_backward():
    """Kernel-path fwd+bwd vs the masked jnp reference across p_f/p_o/p_s
    mixes: wall time per fwd+grad call, the executed-MXU-FLOP account of
    the gate-aware kernels (static HLO FLOP counts cannot see runtime
    ``@pl.when`` skips — the interpret-mode grid lowers to a loop whose body
    XLA counts once; see docs/kernels.md), and the dispatched-bytes
    fraction of the compaction dispatch (live-slice grids, exact live
    counts as bounds). Besides the CSV rows, writes machine-readable
    ``BENCH_kernel_backward.json`` so the perf trajectory is tracked
    across PRs (``make bench-json``)."""
    import json

    from repro.kernels.d2ft_attention import (
        BWD_MATMULS_PER_TILE, gated_attention_dispatched_bytes,
        gated_attention_flops)
    from repro.kernels.ops import gated_attention
    from repro.kernels.ref import gated_attention_ref

    B, H, S, hd = 4, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, ct = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks)
    rng = np.random.default_rng(0)
    ones = np.ones((B, H))
    full_fwd, full_bwd = gated_attention_flops(ones, ones, S, hd, causal=True)
    full_fb, full_bb = gated_attention_dispatched_bytes(ones, ones, S, hd)

    def timed(fn):
        jax.block_until_ready(fn(q, k, v))          # compile + warm
        n = 3
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(q, k, v))
        return (time.perf_counter() - t0) / n * 1e6

    records = []
    # micro-batch mixes as (p_f, p_o, p_s) fractions of the (B, H) subnets
    for name, probs in [("pf5_po0_ps0", (1.0, 0.0, 0.0)),
                        ("pf3_po1_ps1", (0.6, 0.2, 0.2)),
                        ("pf1_po2_ps2", (0.2, 0.4, 0.4))]:
        ops_ = rng.choice(3, size=(B, H), p=probs)
        g_f = jnp.asarray((ops_ != 2).astype(np.float32))
        g_b = jnp.asarray((ops_ == 0).astype(np.float32))
        # exact live counts double as the static compaction bounds, so the
        # benchmark measures the live-slice grids the train loop dispatches
        live_f = max(1, int((ops_ != 2).sum()))
        live_b = max(1, int((ops_ == 0).sum()))

        def loss_kernel(q, k, v):
            # interpret auto-detects: compiled on TPU, interpreter on CPU
            out = gated_attention(q, k, v, g_f, g_b, live_fwd=live_f,
                                  live_bwd=live_b)
            return (out * ct).sum()

        def loss_ref(q, k, v):
            out = gated_attention_ref(q, k, v, g_f, g_b)
            return (out * ct).sum()

        kern = jax.jit(jax.value_and_grad(loss_kernel, argnums=(0, 1, 2)))
        refp = jax.jit(jax.value_and_grad(loss_ref, argnums=(0, 1, 2)))
        kern_us, ref_us = timed(kern), timed(refp)
        e_fwd, e_bwd = gated_attention_flops(np.asarray(g_f), np.asarray(g_b),
                                             S, hd, causal=True)
        d_fwd, d_bwd = gated_attention_dispatched_bytes(
            np.asarray(g_f), np.asarray(g_b), S, hd, live_fwd=live_f,
            live_bwd=live_b)
        flop_frac = (e_fwd + e_bwd) / (full_fwd + full_bwd)
        byte_frac = (d_fwd + d_bwd) / (full_fb + full_bb)
        emit(f"kernel_bwd_{name}", kern_us,
             f"ref_us={ref_us:.1f};executed_mxu_gflop={(e_fwd + e_bwd) / 1e9:.3f};"
             f"full_mxu_gflop={(full_fwd + full_bwd) / 1e9:.3f};"
             f"executed_fraction={flop_frac:.3f};"
             f"dispatched_bytes_fraction={byte_frac:.3f}")
        records.append({
            "mix": name,
            "p_fractions": {"p_f": probs[0], "p_o": probs[1], "p_s": probs[2]},
            "wall_us_per_call": kern_us,
            "ref_wall_us_per_call": ref_us,
            "dispatched_slices": {"fwd": live_f, "bwd": live_b, "total": B * H},
            "executed_mxu_flops": e_fwd + e_bwd,
            "full_mxu_flops": full_fwd + full_bwd,
            "executed_flop_fraction": flop_frac,
            "dispatched_bytes": {"fwd": d_fwd, "bwd": d_bwd},
            "full_dispatched_bytes": {"fwd": full_fb, "bwd": full_bb},
            "dispatched_bytes_fraction": byte_frac,
        })
    # ---- SSD / RG-LRU / MoE block-kernel mixes (contract parity with the
    # attention rows: wall time vs the masked reference, executed-FLOP and
    # dispatched-bytes fractions from each kernel's analytic account).
    # Appended AFTER the attention mixes so baseline indices 0-2 are stable.
    from repro.kernels.d2ft_moe import (gated_moe_dispatched_bytes,
                                        gated_moe_flops)
    from repro.kernels.d2ft_rglru import (gated_rglru_dispatched_bytes,
                                          gated_rglru_flops)
    from repro.kernels.d2ft_ssd import (gated_ssd_dispatched_bytes,
                                        gated_ssd_flops)
    from repro.kernels.ops import gated_moe_ffn, gated_rglru_scan, \
        gated_ssd_scan
    from repro.kernels.ref import (gated_moe_ffn_ref, gated_rglru_ref,
                                   gated_ssd_ref)

    def timed_on(fn, *args):
        jax.block_until_ready(fn(*args))            # compile + warm
        n = 3
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / n * 1e6

    def mix_gates(shape, probs):
        ops_ = rng.choice(3, size=shape, p=probs)
        return (ops_, jnp.asarray((ops_ != 2).astype(np.float32)),
                jnp.asarray((ops_ == 0).astype(np.float32)),
                max(1, int((ops_ != 2).sum())), max(1, int((ops_ == 0).sum())))

    block_mixes = [("pf3_po1_ps1", (0.6, 0.2, 0.2)),
                   ("pf1_po2_ps2", (0.2, 0.4, 0.4))]

    def record(kernel, name, probs, kern_us, ref_us, disp, e_flops, f_flops,
               d_bytes, f_bytes):
        flop_frac = e_flops / f_flops
        byte_frac = sum(d_bytes) / sum(f_bytes)
        emit(f"kernel_bwd_{kernel}_{name}", kern_us,
             f"ref_us={ref_us:.1f};executed_mxu_gflop={e_flops / 1e9:.3f};"
             f"full_mxu_gflop={f_flops / 1e9:.3f};"
             f"executed_fraction={flop_frac:.3f};"
             f"dispatched_bytes_fraction={byte_frac:.3f}")
        records.append({
            "mix": f"{kernel}_{name}", "kernel": kernel,
            "p_fractions": {"p_f": probs[0], "p_o": probs[1],
                            "p_s": probs[2]},
            "wall_us_per_call": kern_us,
            "ref_wall_us_per_call": ref_us,
            "dispatched_slices": disp,
            "executed_mxu_flops": e_flops,
            "full_mxu_flops": f_flops,
            "executed_flop_fraction": flop_frac,
            "dispatched_bytes": {"fwd": d_bytes[0], "bwd": d_bytes[1]},
            "full_dispatched_bytes": {"fwd": f_bytes[0], "bwd": f_bytes[1]},
            "dispatched_bytes_fraction": byte_frac,
        })

    # SSD chunked scan: (sample, head) slices
    Bs, Hs, Ss, Ps, Ns, Qs = 4, 8, 256, 16, 16, 64
    kss = jax.random.split(jax.random.PRNGKey(1), 5)
    xs = jax.random.normal(kss[0], (Bs, Ss, Hs, Ps))
    das = -jax.nn.softplus(jax.random.normal(kss[1], (Bs, Ss, Hs)))
    Bms = jax.random.normal(kss[2], (Bs, Ss, Ns)) * 0.5
    Cms = jax.random.normal(kss[3], (Bs, Ss, Ns)) * 0.5
    cts = jax.random.normal(kss[4], (Bs, Ss, Hs, Ps))
    ones_s = np.ones((Bs, Hs))
    sflops_full = sum(gated_ssd_flops(ones_s, ones_s, Ss, Ps, Ns, chunk=Qs))
    sbytes_full = gated_ssd_dispatched_bytes(ones_s, ones_s, Ss, Ps, Ns,
                                             chunk=Qs)
    for name, probs in block_mixes:
        ops_, g_f, g_b, live_f, live_b = mix_gates((Bs, Hs), probs)
        kern = jax.jit(jax.value_and_grad(
            lambda x, da, Bm, Cm: (gated_ssd_scan(
                x, da, Bm, Cm, g_f, g_b, chunk=Qs, live_fwd=live_f,
                live_bwd=live_b) * cts).sum(), argnums=(0, 1, 2, 3)))
        refp = jax.jit(jax.value_and_grad(
            lambda x, da, Bm, Cm: (gated_ssd_ref(
                x, da, Bm, Cm, g_f, g_b, chunk=Qs) * cts).sum(),
            argnums=(0, 1, 2, 3)))
        e_flops = sum(gated_ssd_flops(np.asarray(g_f), np.asarray(g_b),
                                      Ss, Ps, Ns, chunk=Qs))
        d_bytes = gated_ssd_dispatched_bytes(
            np.asarray(g_f), np.asarray(g_b), Ss, Ps, Ns, chunk=Qs,
            live_fwd=live_f, live_bwd=live_b)
        record("ssd", name, probs, timed_on(kern, xs, das, Bms, Cms),
               timed_on(refp, xs, das, Bms, Cms),
               {"fwd": live_f, "bwd": live_b, "total": Bs * Hs},
               e_flops, sflops_full, d_bytes, sbytes_full)

    # RG-LRU recurrence: (sample, channel-band) slices
    Br, Sr, Wr, Gr, Qr = 4, 256, 256, 8, 64
    Wgr = Wr // Gr
    krs = jax.random.split(jax.random.PRNGKey(2), 3)
    lar = -jax.nn.softplus(jax.random.normal(krs[0], (Br, Sr, Wr)))
    br = jax.random.normal(krs[1], (Br, Sr, Wr))
    ctr = jax.random.normal(krs[2], (Br, Sr, Wr))
    ones_r = np.ones((Br, Gr))
    rflops_full = sum(gated_rglru_flops(ones_r, ones_r, Sr, Wgr, chunk=Qr))
    rbytes_full = gated_rglru_dispatched_bytes(ones_r, ones_r, Sr, Wgr,
                                               chunk=Qr)
    for name, probs in block_mixes:
        ops_, g_f, g_b, live_f, live_b = mix_gates((Br, Gr), probs)
        kern = jax.jit(jax.value_and_grad(
            lambda la, b: (gated_rglru_scan(
                la, b, g_f, g_b, chunk=Qr, live_fwd=live_f,
                live_bwd=live_b) * ctr).sum(), argnums=(0, 1)))
        refp = jax.jit(jax.value_and_grad(
            lambda la, b: (gated_rglru_ref(la, b, g_f, g_b,
                                           chunk=Qr) * ctr).sum(),
            argnums=(0, 1)))
        e_flops = sum(gated_rglru_flops(np.asarray(g_f), np.asarray(g_b),
                                        Sr, Wgr, chunk=Qr))
        d_bytes = gated_rglru_dispatched_bytes(
            np.asarray(g_f), np.asarray(g_b), Sr, Wgr, chunk=Qr,
            live_fwd=live_f, live_bwd=live_b)
        record("rglru", name, probs, timed_on(kern, lar, br),
               timed_on(refp, lar, br),
               {"fwd": live_f, "bwd": live_b, "total": Br * Gr},
               e_flops, rflops_full, d_bytes, rbytes_full)

    # MoE expert FFN: (expert, capacity-block) tiles, live slots packed
    # first per expert (mirrors the model's gate-aware dispatch) so the
    # live_slots capacity truncation is real
    Em, Cm_, Dm, Fm, bcm = 4, 256, 64, 128, 32
    ncb = Cm_ // bcm
    kms = jax.random.split(jax.random.PRNGKey(3), 5)
    xbm = jax.random.normal(kms[0], (Em, Cm_, Dm))
    wum = jax.random.normal(kms[1], (Em, Dm, Fm)) / np.sqrt(Dm)
    wgm = jax.random.normal(kms[2], (Em, Dm, Fm)) / np.sqrt(Dm)
    wdm = jax.random.normal(kms[3], (Em, Fm, Dm)) / np.sqrt(Fm)
    ctm = jax.random.normal(kms[4], (Em, Cm_, Dm))
    mflops_full = sum(gated_moe_flops(np.ones((Em, ncb)), np.ones((Em, ncb)),
                                      bcm, Dm, Fm))
    mbytes_full = gated_moe_dispatched_bytes(Em, ncb, bcm, Dm, Fm)
    for name, probs in block_mixes:
        ops_ = np.sort(rng.choice(3, size=(Em, ncb), p=probs), axis=1)
        fm_blk = (ops_ != 2).astype(np.float32)
        bm_blk = (ops_ == 0).astype(np.float32)
        fs = jnp.asarray(np.repeat(fm_blk, bcm, axis=1))
        bs = jnp.asarray(np.repeat(bm_blk, bcm, axis=1))
        live_slots = max(bcm, int(fm_blk.sum(axis=1).max()) * bcm)
        ncb_t = live_slots // bcm
        kern = jax.jit(jax.value_and_grad(
            lambda xb, wu, wg, wd: (gated_moe_ffn(
                xb, wu, wg, wd, fs, bs, block_c=bcm,
                live_slots=live_slots) * ctm).sum(), argnums=(0, 1, 2, 3)))
        refp = jax.jit(jax.value_and_grad(
            lambda xb, wu, wg, wd: (gated_moe_ffn_ref(
                xb, wu, wg, wd, jnp.asarray(fm_blk), jnp.asarray(bm_blk),
                act=jax.nn.silu, block_c=bcm) * ctm).sum(),
            argnums=(0, 1, 2, 3)))
        e_flops = sum(gated_moe_flops(fm_blk, bm_blk, bcm, Dm, Fm))
        d_bytes = gated_moe_dispatched_bytes(Em, ncb_t, bcm, Dm, Fm)
        record("moe", name, probs, timed_on(kern, xbm, wum, wgm, wdm),
               timed_on(refp, xbm, wum, wgm, wdm),
               {"fwd": int(fm_blk.sum()), "bwd": int(bm_blk.sum()),
                "total": Em * ncb},
               e_flops, mflops_full, d_bytes, mbytes_full)

    payload = {
        "bench": "kernel_backward",
        "shape": {"B": B, "H": H, "S": S, "head_dim": hd},
        "backward_matmuls_per_tile": BWD_MATMULS_PER_TILE,
        "backend": jax.default_backend(),
        "mixes": records,
    }
    with open(BENCH_KERNEL_BACKWARD_JSON, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {BENCH_KERNEL_BACKWARD_JSON}", file=sys.stderr)


# ------------------------------------------- distributed-step comm savings
def _refuse_on_tpu(name: str):
    """The distributed_step / elastic entries are 8-host-device CPU
    emulations run in a child process. On a TPU backend this process holds
    the chip, so the child could only measure the CPU: refuse instead."""
    if jax.default_backend() == "tpu":
        raise SystemExit(
            f"{name} is an 8-host-device CPU emulation and does not run on "
            "a TPU backend (this process holds the chip; a child would "
            "measure the CPU). Run it where JAX_PLATFORMS=cpu.")


def bench_distributed_step():
    """Paper Eq. 4 executed: the shard_map gated train step on an
    8-host-device CPU mesh over a schedule x sync-mode matrix — paper-mix
    (40% p_f / 30% p_o / 30% p_s, concentrated) and uniform-half (spread)
    schedules under the masked psum and the ZeRO reduce-scatter/all-gather
    sync, vs the all-p_f baseline. Reports wall time per step, per-device
    collective bytes parsed from compiled HLO, the sync plan's wire-byte
    model, and the ``zero_sync`` summary (wire fractions + sharded-moment
    memory). Runs ``benchmarks/dist_step.py`` in a subprocess because the
    forced host-device count must be set before jax initializes (this
    process already locked its backend). Writes
    ``BENCH_distributed_step.json``."""
    import os
    import subprocess

    _refuse_on_tpu("distributed_step")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # dist_step.py appends the host-device-count flag to XLA_FLAGS itself
    proc = subprocess.run([sys.executable, "-m", "benchmarks.dist_step"],
                          env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise RuntimeError("benchmarks.dist_step failed")
    for line in proc.stdout.splitlines():
        if line.strip():
            print(line)
    sys.stderr.write(proc.stderr)


# ------------------------------------------ elastic fault-tolerance matrix
def bench_elastic():
    """The four fault scenarios of docs/robustness.md through the elastic
    loop on an 8-host-device CPU mesh: straggler-aware replanning
    (mitigation ratio of the capacity-constrained makespan), device-dropout
    recovery (steps replayed + resume-parity error vs a survivors-only
    run), the NaN-burst gradient guard (steps skipped + loss gap vs the
    fault-free run), and the lo-fi local fallback after dropped sync
    rounds (merge count + progress). Runs ``benchmarks/elastic.py`` in a
    subprocess because the forced host-device count must be set before jax
    initializes. Writes ``BENCH_elastic.json`` (gated by
    ``tools/check_bench.py``)."""
    import os
    import subprocess

    _refuse_on_tpu("elastic")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-m", "benchmarks.elastic"],
                          env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise RuntimeError("benchmarks.elastic failed")
    for line in proc.stdout.splitlines():
        if line.strip():
            print(line)
    sys.stderr.write(proc.stderr)


# ---------------------------------------------- paged-KV serving throughput
def bench_serving():
    """Gate-aware serving: a synthetic mixed-length request trace through
    the continuous-batching engine over the paged KV cache — tokens/sec,
    per-token latency p50/p99, the request-level knapsack wave plan and
    peak page occupancy. Deterministic counters are gated tightly, wall
    clock generously. Writes ``BENCH_serving.json``; see
    benchmarks/serving.py for the trace and engine geometry."""
    from benchmarks import serving
    serving.main([])


BENCHES = {
    "workload_variance": bench_workload_variance,
    "execution_time": bench_execution_time,
    "accuracy_vs_cost": bench_accuracy_vs_cost,
    "score_combos": bench_score_combos,
    "fwd_bwd_ratio": bench_fwd_bwd_ratio,
    "num_subnets": bench_num_subnets,
    "microbatch_size": bench_microbatch_size,
    "heterogeneous": bench_heterogeneous,
    "po_sweep": bench_po_sweep,
    "bilevel_vs_scaler": bench_bilevel_vs_scaler,
    "lora": bench_lora,
    "kernel_backward": bench_kernel_backward,
    "distributed_step": bench_distributed_step,
    "elastic": bench_elastic,
    "serving": bench_serving,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names (see --list)")
    ap.add_argument("--list", action="store_true",
                    help="print the available benchmark names and exit")
    args = ap.parse_args()
    if args.list:
        print("\n".join(BENCHES))
        return
    names = list(BENCHES) if args.only is None else args.only.split(",")
    unknown = sorted(set(names) - set(BENCHES))
    if unknown:
        # fail loudly: a typo'd --only used to run zero benchmarks and
        # exit 0, which reads as "all green" in a script
        ap.error(f"unknown benchmark(s): {', '.join(unknown)}\n"
                 f"valid names: {', '.join(BENCHES)}")
    enable_compile_cache()
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()


if __name__ == "__main__":
    main()
