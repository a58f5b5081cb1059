"""Operations and bytes a step needs, computed from shapes alone.

These are the yardstick for ``*_roofline`` and ``mfu.*``: they count what
the computation requires, never what a kernel happened to do, so a
smarter kernel cannot read above 100%.

* Operations: 2 per multiply-accumulate, counting only the kept weights
  of an N:M matrix (a kernel that multiplies zeros gets no credit), the
  dense layers, and attention over each token's real context.
* Bytes (the floor the format allows): kept values at their dtype,
  ceil(log2 m) bits per kept value for its index, each activation read
  once and each output written once.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Gemm:
    """y (m, n) = x (m, k) @ W (k, n), W N:M along k when ``nm`` is set."""

    m: int
    k: int
    n: int
    nm: tuple = None          # (n, m) of the pattern, None for dense
    val_bytes: int = 2        # bytes of one stored weight value
    act_bytes: int = 2
    out_bytes: int = 2
    in_bytes: int = None      # bytes of the input read once (default m*k)

    @property
    def kept(self) -> float:
        dens = self.nm[0] / self.nm[1] if self.nm else 1.0
        return self.k * self.n * dens

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.kept

    @property
    def bytes(self) -> float:
        idx_bits = math.ceil(math.log2(self.nm[1])) if self.nm else 0
        weight = self.kept * (self.val_bytes + idx_bits / 8.0)
        x = self.in_bytes if self.in_bytes is not None else \
            self.m * self.k * self.act_bytes
        return weight + x + self.m * self.n * self.out_bytes


def floor_seconds(gemms, peak: dict) -> float:
    """Least time of the calls on the chip: each call bound by its larger
    term, operations over peak FLOP/s or bytes over HBM bandwidth."""
    return sum(max(g.flops / peak["bf16_flops_per_s"],
                   g.bytes / peak["hbm_bytes_per_s"]) for g in gemms)


def roofline_percent(gemms, kernel_seconds: float, peak: dict):
    """Share (%) of the roofline the kernels reached; None without time."""
    if kernel_seconds <= 0:
        return None
    return 100.0 * floor_seconds(gemms, peak) / kernel_seconds


def mfu_percent(flops: float, seconds: float, peak: dict) -> float:
    return 100.0 * flops / seconds / peak["bf16_flops_per_s"]


# ---- decoder language model (reference.llama sizes) ----------------------

def lm_layer_gemms(cfg: dict, rows: int) -> list:
    """The projections of one decoder layer at ``rows`` token rows."""
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    sp = cfg["sparsity"]
    out = []
    for (k, n), target in (((d, q), "attn_proj"), ((d, kv), "attn_proj"),
                           ((d, kv), "attn_proj"), ((q, d), "attn_proj"),
                           ((d, f), "ffn"), ((d, f), "ffn"), ((f, d), "ffn")):
        nm = (sp["n"], sp["m"]) if target in sp["targets"] else None
        out.append(Gemm(rows, k, n, nm))
    return out


def lm_sparse_step_gemms(cfg: dict, rows: int) -> list:
    """The N:M GEMMs of one step over all layers (what the kernels run)."""
    layer = [g for g in lm_layer_gemms(cfg, rows) if g.nm]
    return layer * cfg["num_hidden_layers"]


def lm_token_flops(cfg: dict, context: int) -> float:
    """Required operations of one token whose attention sees ``context``
    keys: every layer's projections, attention, and the output head."""
    proj = sum(g.flops for g in lm_layer_gemms(cfg, 1))
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * context
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (proj + attn) + head


# ---- bottleneck CNN (reference.resnet sizes) -----------------------------

def cnn_conv_gemms(cfg: dict, convs: list, batch: int, sparse: bool) -> list:
    """im2col GEMMs of the convs (N:M ones if ``sparse``, else the dense
    ones); the input floor is the activation, read once, not its patches."""
    sp = cfg["sparsity"]
    out = []
    for c in convs:
        if c["sparse"] != sparse:
            continue
        m = batch * c["h_out"] * c["h_out"]
        k = c["k"] * c["k"] * c["c_in"]
        out.append(Gemm(m, k, c["c_out"],
                        (sp["n"], sp["m"]) if c["sparse"] else None,
                        in_bytes=batch * c["h_in"] * c["h_in"] * c["c_in"] * 2))
    return out


def cnn_image_flops(cfg: dict, convs: list) -> float:
    """Required operations of one image: every conv and the head."""
    conv = sum(g.flops for s in (True, False)
               for g in cnn_conv_gemms(cfg, convs, 1, s))
    return conv + 2.0 * cfg["stages"][-1][1] * cfg["num_classes"]
