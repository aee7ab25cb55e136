"""Model FLOPs of one training example of ONE CHIP'S SHARE of the
Trinity-Mini decoder (`model_type: afmoe`: sliding-window layers and full
causal layers mixed, grouped heads, an output gate from a projection of its
own, a leading dense gated MLP, then a share of a sigmoid-routed expert layer
beside one shared expert), from the configuration's shapes alone, and the
operations and bytes of the windowed attention kernels, of the full ones and
of the held experts' grouped matmuls for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, and the
backward pass as twice the forward. Attention: the VISIBLE pairs of query and
key, whatever tiles a kernel computes them in (`mellum2_swa_moe.py::
visible_pairs`): a full causal layer has `T (T + 1) / 2` (8,390,656 at 4096),
a windowed layer `W (W + 1) / 2 + (T - W) W` (6,292,480 at 4096 / 2048: 75.0%
of the triangle), each pair one multiply-add a head dim in the score product
and one in the context product. The gate's projection is a fifth projection
of the mixer, `d x heads x head_dim`. Experts: the assignments this chip's
`experts_held` of `n_expert` experts get under even routing, `top_k *
experts_held / n_expert` a token (0.5 at 8 of 128, top-8), the shared expert
and the router at its published width for every token. Not counted: the
embedding look-up and its scale, softmax, the norms, rotary, the repeat of
the key and value heads, the gate's sigmoid and product, the router's sigmoid
and top-k, sorts and gathers, the bias update, the optimizer, and anything
the program computes twice.

Multiply-adds per token at the published widths (d 2048, 32 query heads over
4 key-value heads of 128, 4096 tokens, window 2048). Projections: W_q, W_g
and W_o 2048 x 4096 = 8.39 M each, W_k and W_v 2048 x 512 = 1.05 M each:
27.26 M. Attention: 2 x 32 x 128 x pairs / T = 12.58 M a windowed layer
(1536.25 visible keys a query), 16.78 M the full one (2048.5). The dense MLP
3 x 2048 x 6144 = 37.75 M. An expert layer: router 0.26 M, shared 3 x 2048 x
1024 = 6.29 M, routed 0.5 x 3 x 2048 x 1024 = 3.15 M: 9.70 M. The head, once,
2048 x 25024 = 51.25 M.
"""

# The attention kernels are counted by Mellum2's functions (the kernels are
# the same: seven T x T products a head over the visible pairs; q, dq, Out,
# dOut and the REPEATED k, v, dk, dv once each in bf16), the held experts'
# grouped matmuls as `qwen3_next_hybrid.py` counts them (nine products a
# layer over the held experts' assignments under even routing, 2048 a layer
# here; its `n_layer` is the number of expert layers).
from flops.mellum2_swa_moe import (KINDS, PERIOD, attention_counts,
                                   visible_pairs)
from flops.qwen3_next_hybrid import share_expert_counts


def flops_per_example(seq_len, vocab_size=200192, n_layer=32, n_dense_layer=2,
                      d_model=2048, d_dense=6144, n_head=32, n_kv_head=4,
                      head_dim=128, layer_types=PERIOD, sliding_window=2048,
                      n_expert=128, top_k=8, d_expert=1024, n_shared=1,
                      experts_held=None, **_):
    t, d = seq_len, d_model
    held = n_expert if experts_held is None else experts_held
    kinds = [layer_types[i % len(layer_types)] for i in range(n_layer)]
    n_window, n_full = kinds.count(KINDS[0]), kinds.count(KINDS[1])
    n_moe = n_layer - n_dense_layer
    per_token = {
        # W_q, W_g and W_o at the query heads, W_k and W_v at the kv heads
        "projections": 3 * d * n_head * head_dim
        + 2 * d * n_kv_head * head_dim,
        "gate_projection": d * n_head * head_dim,       # of the above
        # QK^T and PV over the visible pairs, averaged over the queries
        "window_attention":
            2 * n_head * head_dim * visible_pairs(t, sliding_window) // t,
        "full_attention": 2 * n_head * head_dim * visible_pairs(t) // t,
        "dense_mlp": 3 * d * d_dense,
        "router": d * n_expert,
        "shared_experts": 3 * d * n_shared * d_expert,
        "routed_experts": top_k * held * 3 * d * d_expert // n_expert,
    }
    kernels = n_window * per_token["window_attention"] \
        + n_full * per_token["full_attention"]
    mixers = n_layer * per_token["projections"] + kernels
    experts = per_token["router"] + per_token["shared_experts"] \
        + per_token["routed_experts"]
    head = d * vocab_size
    total = mixers + n_dense_layer * per_token["dense_mlp"] \
        + n_moe * experts + head
    fwd = 2 * total * t
    windowed = attention_counts(t, n_window, n_head, head_dim, sliding_window)
    full = attention_counts(t, n_full, n_head, head_dim)
    share = share_expert_counts(seq_len, n_moe, d_model, n_expert, held,
                                top_k, d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"window_attention": n_window,
                       "full_attention": n_full, "dense": n_dense_layer,
                       "moe": n_moe},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "mixers_share": mixers / total,
            "projections_share": n_layer * per_token["projections"] / total,
            "attention_kernels_share": kernels / total,
            "dense_mlp_share":
                n_dense_layer * per_token["dense_mlp"] / total,
            "shared_experts_share":
                n_moe * per_token["shared_experts"] / total,
            "routed_experts_share":
                n_moe * per_token["routed_experts"] / total,
            "experts_share": n_moe * experts / total,
            "head_share": head / total,
            "window_visible_pairs": visible_pairs(t, sliding_window),
            "full_visible_pairs": visible_pairs(t),
            "window_attention_flops": windowed["flops"],
            "window_attention_bytes": windowed["bytes"],
            "full_attention_flops": full["flops"],
            "full_attention_bytes": full["bytes"],
            "share_expert_flops": share["flops"],
            "share_expert_bytes": share["bytes"],
            "share_expert_rows": share["rows"]}
