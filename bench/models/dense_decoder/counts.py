"""Operations and bytes that a dense decoder's work needs, from its shapes
alone.

A configuration names this file as its ``counts``. What the harness and
the metric readers call: ``round_flops(cell, rnd)``, the model FLOPs of one
co-located round, and, for the paged decode kernel's roofline, ``dims``,
``decode_attention_bytes`` and ``decode_attention_flops``.

Model FLOPs count the multiply-adds of the algorithm (2 per multiply-add),
not what the program happens to execute: recomputation, padding, inactive
slots and the upper half of a causal score matrix are not counted.
"""

from __future__ import annotations

from typing import Dict, Sequence


def dims(cfg) -> Dict[str, int]:
    return dict(L=cfg.num_layers, d=cfg.d_model, H=cfg.num_heads,
                KV=cfg.num_kv_heads, hd=cfg.head_dim, ff=cfg.d_ff,
                V=cfg.vocab_size)


def layer_matmul_params(d: int, H: int, KV: int, hd: int, ff: int) -> int:
    """Weights one token multiplies through in one dense decoder layer."""
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def lora_params(d: int, H: int, KV: int, hd: int, ff: int, r: int,
                targets: Sequence[str]) -> int:
    shapes = {"q": (d, H * hd), "k": (d, KV * hd), "v": (d, KV * hd),
              "o": (H * hd, d), "gate": (d, ff), "up": (d, ff),
              "down": (ff, d)}
    return sum(r * (shapes[t][0] + shapes[t][1]) for t in targets)


def decode_flops(m: Dict, contexts: Sequence[int]) -> float:
    """One decode token for each active slot; ``contexts`` are the keys each
    one attends to (its cache entries, the new token included). Per slot:
    the layers' and the output head's weights, and QK^T plus PV over the
    context."""
    per_tok = m["L"] * layer_matmul_params(m["d"], m["H"], m["KV"], m["hd"],
                                           m["ff"]) + m["V"] * m["d"]
    attn = sum(4 * m["L"] * m["H"] * m["hd"] * c for c in contexts)
    return 2.0 * per_tok * len(contexts) + attn


def unit_kind(unit_idx: int, n_layers: int, accum: int) -> str:
    """The finetune unit ``training/peft.py`` runs at this index of an
    iteration: embed | fwd | head | bwd | embed_bwd | opt."""
    upm = 2 * n_layers + 3
    if unit_idx >= accum * upm:
        return "opt"
    u = unit_idx % upm
    if u == 0:
        return "embed"
    if u <= n_layers:
        return "fwd"
    if u == n_layers + 1:
        return "head"
    if u <= 2 * n_layers + 1:
        return "bwd"
    return "embed_bwd"


def unit_flops(m: Dict, kind: str, micro_batch: int, seq_len: int, r: int,
               targets: Sequence[str]) -> float:
    """Model FLOPs of one LoRA finetune unit on a (micro_batch, seq_len)
    micro-batch. fwd: one layer, base and adapter weights, causal
    attention. head: the output projection forward and its activation
    gradient. bwd: one layer's activation gradients through the frozen base
    weights and the adapters, the adapters' weight gradients, and causal
    attention's backward (twice its forward); the recomputed forward is not
    counted. embed, embed_bwd and opt do no matrix work."""
    B, S = micro_batch, seq_len
    T = B * S
    base = layer_matmul_params(m["d"], m["H"], m["KV"], m["hd"], m["ff"])
    ad = lora_params(m["d"], m["H"], m["KV"], m["hd"], m["ff"], r, targets)
    attn_fwd = 2.0 * B * m["H"] * m["hd"] * S * S    # causal half of 4*S^2
    if kind == "fwd":
        return 2.0 * T * (base + ad) + attn_fwd
    if kind == "head":
        return 4.0 * B * (S - 1) * m["V"] * m["d"]
    if kind == "bwd":
        return 2.0 * T * base + 4.0 * T * ad + 2 * attn_fwd
    return 0.0


def decode_attention_bytes(m: Dict, contexts: Sequence[int], slots: int,
                           page_tokens: int, kv_bytes: int = 2) -> float:
    """HBM bytes one call of the paged decode kernel (one layer) must move:
    the live K and V pages of the active slots, and q and the output of
    every slot in the batch."""
    pages = sum(-(-c // page_tokens) for c in contexts)
    kv = 2 * pages * page_tokens * m["KV"] * m["hd"] * kv_bytes
    return kv + 2 * slots * m["H"] * m["hd"] * kv_bytes


def decode_attention_flops(m: Dict, contexts: Sequence[int]) -> float:
    """QK^T and PV of one kernel call (one layer)."""
    return sum(4.0 * m["H"] * m["hd"] * c for c in contexts)


def round_flops(cell, rnd) -> float:
    """One co-located round: a decode token for each active slot, and the
    ``rnd.k`` finetune units it ran, from unit ``rnd.unit0`` of the
    iteration on."""
    m = dims(cell.cfg)
    f = decode_flops(m, rnd.contexts)
    ft = cell.conf["finetune"]
    for u in range(rnd.k):
        kind = unit_kind((rnd.unit0 + u) % cell.upi, cell.cfg.num_layers,
                         cell.pc.accum)
        f += unit_flops(m, kind, cell.pc.micro_batch, cell.pc.seq_len,
                        ft["lora_rank"], ft["targets"])
    return f
