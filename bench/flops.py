"""Model FLOPs of one training step, from a configuration file and a traffic mix.

Counted: 6 FLOPs per matrix-multiply parameter per token it is applied to
(forward 2, backward 4), and attention's score and context products,
4 * S * S_kv * d per layer and sequence in the forward pass, times 3 for
forward and backward.  Not counted: recomputation, the embedding lookup,
norms, softmax and the optimizer.  Scores are counted whole, causal or not,
as the program computes them.
"""

from __future__ import annotations

from typing import Any, Dict


def _attn_params(d: int) -> int:
    return 4 * d * d


def _ffn_params(d: int, f: int) -> int:
    return 2 * d * f


def step_flops(config: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """FLOPs of one step over all chips of the cell."""
    c = config["config"]
    d, f, V, L = c["d_model"], c["d_ff"], c["vocab"], c["n_layers"]
    rows = traffic["batch_per_chip"] * traffic["chips"]
    S = traffic["seq_len"]
    head = d * V
    if config["family"] == "decoder":
        dense = 6.0 * (L * (_attn_params(d) + _ffn_params(d, f)) + head) * rows * S
        attn = 3.0 * 4.0 * S * S * d * L * rows
        return dense + attn
    if config["family"] == "encdec":
        T = c["enc_dec"]["enc_seq"]
        Le = c["enc_dec"]["n_enc_layers"]
        enc_tok, dec_tok = rows * T, rows * S
        cross_kv = 2 * d * d  # cross-attention keys and values act on the frames
        dense = 6.0 * (
            Le * (_attn_params(d) + _ffn_params(d, f)) * enc_tok
            + L * cross_kv * enc_tok
            + (L * (_attn_params(d) - cross_kv + _attn_params(d) + _ffn_params(d, f)) + head)
            * dec_tok
        )
        attn = 3.0 * 4.0 * d * rows * (Le * T * T + L * S * S + L * S * T)
        return dense + attn
    raise ValueError(f"no FLOP count for family {config['family']!r}")
