"""Operations and bytes the algorithms need, from the configuration's
sizes (the JSON files under ``bench/configs``), never from the program.

Counts are what the mathematics requires: a multiply-add is 2 FLOPs,
recomputation is not counted, and a byte is counted once per pass that
must move it through HBM.
"""
from __future__ import annotations

import re

GROUP = 128  # lanes sharing one 8-bit base exponent in the SFP containers


# -- containers ---------------------------------------------------------------

def payload_bits(container: str) -> int:
    """Bits per value of a packed container's payload (bases excluded)."""
    m = re.fullmatch(r"sfp-m(\d+)e(\d+)", container)
    if m:
        return 1 + int(m.group(1)) + int(m.group(2))
    m = re.fullmatch(r"sfp(8|16)", container)
    if m:
        return int(m.group(1))
    raise ValueError(f"unknown container {container!r}")


def packed_bytes(values: int, container: str) -> float:
    """Payload plus one base byte per 128-lane group."""
    return values * payload_bits(container) / 8 + values / GROUP


# -- mamba2 (SSD) training ----------------------------------------------------

def mamba2_sizes(c: dict) -> dict:
    d = c["hidden_size"]
    di = c["expand"] * d
    P = c["head_dim"]
    return dict(d=d, di=di, N=c["state_size"], P=P, H=di // P,
                G=c["n_groups"], cs=c["chunk_size"], cw=c["conv_kernel"],
                L=c["num_hidden_layers"], V=c["vocab_size"])


def mamba2_matmul_params(c: dict) -> int:
    """Weights that multiply every token: in/out projections of each layer
    and the (tied) output head."""
    s = mamba2_sizes(c)
    per_layer = (s["d"] * (2 * s["di"] + 2 * s["G"] * s["N"] + s["H"])
                 + s["di"] * s["d"])
    return s["L"] * per_layer + s["V"] * s["d"]


def mamba2_ssd_flops_per_token(c: dict) -> int:
    """Chunked SSD of one layer, per token (Mamba-2, section 6): the
    intra-chunk C.B scores and their product with x over the whole chunk,
    the chunk state, and the inter-chunk output."""
    s = mamba2_sizes(c)
    return (2 * s["cs"] * s["N"] * s["G"] + 2 * s["cs"] * s["P"] * s["H"]
            + 4 * s["N"] * s["P"] * s["H"])


def mamba2_train_flops_per_token(c: dict) -> int:
    """Forward plus backward (3x forward), no recomputation."""
    s = mamba2_sizes(c)
    conv = 2 * s["cw"] * (s["di"] + 2 * s["G"] * s["N"])
    fwd = (2 * mamba2_matmul_params(c)
           + s["L"] * (mamba2_ssd_flops_per_token(c) + conv))
    return 3 * fwd


# -- dense GQA decoder (mistral) serving -------------------------------------

def gqa_sizes(c: dict) -> dict:
    return dict(d=c["hidden_size"], H=c["num_attention_heads"],
                KH=c["num_key_value_heads"], hd=c["head_dim"],
                ff=c["intermediate_size"], V=c["vocab_size"],
                L=c["num_hidden_layers"])


def gqa_matmul_params(c: dict) -> int:
    """Weights every decoded token multiplies: attention and gated MLP of
    each layer, and the untied output head."""
    s = gqa_sizes(c)
    attn = 2 * s["d"] * s["H"] * s["hd"] + 2 * s["d"] * s["KH"] * s["hd"]
    mlp = 3 * s["d"] * s["ff"]
    return s["L"] * (attn + mlp) + s["d"] * s["V"]


def kv_bytes_per_token_layer(c: dict, container: str) -> float:
    s = gqa_sizes(c)
    return 2 * packed_bytes(s["KH"] * s["hd"], container)


def attn_decode_flops(c: dict, ctx: int) -> int:
    """QK and PV of one new token against ``ctx`` cached positions, one
    layer."""
    s = gqa_sizes(c)
    return 4 * ctx * s["H"] * s["hd"]


def paged_decode_call(c: dict, container: str, slots: int,
                      ctx_total: float) -> tuple:
    """(FLOPs, bytes) of one paged decode attention call (one layer, every
    slot): the live packed KV, and q in and the output out in bf16."""
    s = gqa_sizes(c)
    qo = 2 * slots * s["H"] * s["hd"] * 2
    return (attn_decode_flops(c, ctx_total),
            ctx_total * kv_bytes_per_token_layer(c, container) + qo)


def decode_step(c: dict, container: str, slots: int,
                ctx_total: float) -> tuple:
    """(FLOPs, bytes) one decode step needs: every weight once (bf16),
    the live packed KV of every layer, and the matmuls of ``slots`` tokens.
    Checksums and idle slots are not work a decode step needs."""
    s = gqa_sizes(c)
    flops = (2 * gqa_matmul_params(c) * slots
             + s["L"] * attn_decode_flops(c, ctx_total))
    byts = (2 * gqa_matmul_params(c)
            + s["L"] * ctx_total * kv_bytes_per_token_layer(c, container))
    return flops, byts


def roofline_time(flops: float, byts: float, peaks) -> tuple:
    """(least seconds, which bound holds)."""
    tf, tb = flops / peaks.bf16_flops, byts / peaks.hbm_bytes
    return (tf, "compute") if tf >= tb else (tb, "memory")
