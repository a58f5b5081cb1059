"""Beyond-paper: roofline terms of the Pallas indexmac kernel vs dense
matmul on TPU v5e constants, over the paper's CNN GEMMs + transformer
projection GEMMs — for both value families (bf16 and the int8 QNMWeight
path, which streams one byte per kept value + a f32 scale per output
channel). Also times the kernels vs their oracles on one shape
(compiled on a TPU, interpreted on the CPU; correctness + a measured
number for the CSV).

``kernel_records()`` returns the machine-readable per-kernel rows that
``benchmarks/run.py`` writes into BENCH_results.json.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.cnn_specs import resnet50_gemms
from repro.core.cost_model import (
    tpu_dense_cost,
    tpu_indexmac_cost,
    tpu_indexmac_q_cost,
)
from repro.core.sparsity import NMConfig, compress_nm, random_nm_matrix
from repro.kernels import autotune
from repro.kernels.backend import interpret_for
from repro.kernels.indexmac.kernel import nm_spmm_pallas, nm_spmm_pallas_q
from repro.kernels.indexmac.ref import nm_matmul_q_ref, nm_matmul_ref

TRANSFORMER_GEMMS = [
    # (name, M=tokens, K, N) — decode-ish (small M) and prefill-ish (large M)
    ("yi_ffn_decode", 16, 4096, 11008),
    ("yi_ffn_prefill", 8192, 4096, 11008),
    ("dsv2_expert_decode", 64, 5120, 1536),
    ("chameleon_qkv_decode", 16, 8192, 10240),
]

# (family tag, cost fn) — the int8 family halves the weight-value bytes
# again on top of the N:M compression.
_FAMILIES = (
    ("bf16", tpu_indexmac_cost),
    ("int8", tpu_indexmac_q_cost),
)


def _gemms():
    return ([("r50_" + t, mm, kk, nn) for t, mm, kk, nn in
             resnet50_gemms()[::12]] + TRANSFORMER_GEMMS)


def kernel_records() -> list[dict]:
    """Per-(N:M, family, GEMM) roofline accounting, machine-readable."""
    out = []
    for cfg in (NMConfig(2, 4), NMConfig(1, 4)):
        for vtag, cost_fn in _FAMILIES:
            for name, m, k, n in _gemms():
                dense = tpu_dense_cost(m, k, n)
                sp = cost_fn(m, k, n, cfg)
                t_d = max(dense.t_mem(), dense.t_compute())
                t_s = max(sp.t_mem(), sp.t_compute())
                out.append({
                    "nm": cfg.tag,
                    "family": vtag,
                    "gemm": name,
                    "m": m, "k": k, "n": n,
                    "hbm_bytes": sp.hbm_bytes,
                    "dense_hbm_bytes": dense.hbm_bytes,
                    "bytes_vs_dense": sp.hbm_bytes / dense.hbm_bytes,
                    "roofline_speedup_vs_dense": t_d / t_s,
                    "bound": ("mem" if sp.t_mem() > sp.t_compute()
                              else "comp"),
                })
    return out


def run(verbose=True):
    rows = []
    for r in kernel_records():
        rows.append((f"{r['nm']}-{r['family']}", r["gemm"],
                     r["roofline_speedup_vs_dense"], r["bytes_vs_dense"],
                     r["bound"]))
        if verbose:
            print(f"  tpu {r['nm']} {r['family']} {r['gemm']:22s} bytes x"
                  f"{r['bytes_vs_dense']:.2f} "
                  f"roofline speedup {r['roofline_speedup_vs_dense']:.2f}x "
                  f"({r['bound']}-bound)")
    return rows


def timed_correctness(cfgs=(NMConfig(2, 4), NMConfig(1, 4))):
    """Autotune the block triple for one shape, then time the winners —
    per N:M pattern and per value family, since all four rows feed the
    bench regression gate and must be independent measurements
    (interpret mode on CPU: the numbers are smoke signals, not TPU
    measurements — the same sweeps persist real timings on hardware)."""
    from benchmarks.measured import best_us

    out = {}
    k, n, m = 1024, 512, 128
    for cfg in cfgs:
        bm, bn, bk = autotune.ensure_tuned(m, n, k, cfg, dtype=jnp.float32)
        w = random_nm_matrix(jax.random.PRNGKey(0), (k, n), cfg, axis=0)
        vals, idx = compress_nm(w, cfg, axis=0)
        x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
        y_ref = nm_matmul_ref(x, vals, idx, cfg)
        f = lambda: nm_spmm_pallas(  # noqa: E731
            x, vals, idx, cfg=cfg, block_m=bm, block_n=bn, block_k=bk,
            interpret=interpret_for("tpu"))
        y = f().block_until_ready()
        us = best_us(f, repeats=3)
        err = float(jnp.abs(y - y_ref).max())
        assert err < 1e-3, err
        out[(cfg.tag, "bf16")] = (us, err, (bm, bn, bk))

        # int8 family: its own autotune keys (value dtype int8), its own
        # timer.
        qbm, qbn, qbk = autotune.ensure_tuned(m, n, k, cfg, dtype=jnp.int8)
        scales = jnp.max(jnp.abs(vals), axis=0) / 127.0
        qvals = jnp.clip(jnp.round(vals / scales[None, :]), -127,
                         127).astype(jnp.int8)
        yq_ref = nm_matmul_q_ref(x, qvals, idx, scales, cfg)
        fq = lambda: nm_spmm_pallas_q(  # noqa: E731
            x, qvals, idx, scales, cfg=cfg, block_m=qbm, block_n=qbn,
            block_k=qbk, interpret=interpret_for("tpu"))
        yq = fq().block_until_ready()
        us_q = best_us(fq, repeats=3)
        err_q = float(jnp.abs(yq - yq_ref).max())
        assert err_q < 1e-3, err_q
        out[(cfg.tag, "int8")] = (us_q, err_q, (qbm, qbn, qbk))
    return out


def main():
    rows = run()
    timed = timed_correctness()
    out = []
    for tag in ("2:4", "1:4"):
        for vtag in ("bf16", "int8"):
            fam = f"{tag}-{vtag}"
            dec = [r for r in rows if r[0] == fam and "decode" in r[1]]
            avg = float(np.mean([r[2] for r in dec]))
            us, _, block = timed[(tag, vtag)]
            print(f"tpu_kernel {fam}: decode-GEMM roofline speedup avg "
                  f"{avg:.2f}x (weight-bytes x"
                  f"{float(np.mean([r[3] for r in dec])):.2f})")
            out.append((f"tpu_kernel_{fam}_decode", us,
                        f"roofline_speedup={avg:.3f};block={block[0]}x"
                        f"{block[1]}x{block[2]}"))
    return out


if __name__ == "__main__":
    main()
