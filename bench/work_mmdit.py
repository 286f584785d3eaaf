"""Multiply-adds of one MMDiT forward from a configuration file's sizes
(``bench/families/mmdit.py``'s keys), for ``model.mfu.mmdit.batch``.

Counted from shapes, never read from the program: both streams' linears,
the joint attention over ``[image; text]`` tokens (its full square, which
the last block computes too, though only the image rows are used), the
pre-only last block's text stream (2d modulation and q/k/v only), and the
float32 embeddings and head.
"""
from __future__ import annotations


def mmdit_macs(config: dict) -> int:
    """Multiply-adds of one MMDiT forward for one image."""
    d = config["hidden_size"]
    p, c = config["patch_size"], config["in_channels"]
    ff = int(config["mlp_ratio"] * d)
    ti = (config["input_size"] // p) ** 2
    tt = config["context_tokens"]
    n = ti + tt
    layers = config["depth"]

    def stream(tokens):  # a full stream: 6d modulation, q/k/v/o, MLP
        return d * 6 * d + 4 * tokens * d * d + 2 * tokens * d * ff

    attention = 2 * n * n * d  # q k^T and p v over the joint tokens
    full = stream(ti) + stream(tt) + attention
    last = stream(ti) + d * 2 * d + 3 * tt * d * d + attention
    embed = (ti * p * p * c * d  # patches
             + 256 * d + d * d  # timestep MLP
             + config["pooled_dim"] * d + d * d  # pooled-text MLP
             + tt * config["context_dim"] * d)  # context embedding
    head = d * 2 * d + ti * d * p * p * c
    return (layers - 1) * full + last + embed + head
