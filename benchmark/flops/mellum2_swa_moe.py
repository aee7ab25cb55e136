"""Model FLOPs of one training example of ONE CHIP'S SHARE of the Mellum2
decoder (sliding-window and full causal attention layers mixed, grouped heads,
a share of a renormalised top-k expert layer in every layer, no shared expert,
no dense layer), from the configuration's shapes alone, and the operations
and bytes of the windowed attention kernels, of the full ones and of the held
experts' grouped matmuls for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, and the
backward pass as twice the forward. Attention: the VISIBLE pairs of query and
key, whatever tiles a kernel computes them in: a full causal layer has
`T (T + 1) / 2` (33,558,528 at 8192), a windowed layer `sum_i min(i + 1, W)
= W (W + 1) / 2 + (T - W) W` (7,864,832 at 8192 / 1024: 23.4% of the
triangle), each pair one multiply-add a head dim in the score product and one
in the context product. Experts: the assignments this chip's `experts_held`
of `n_expert` experts get under even routing, `top_k * experts_held /
n_expert` a token (1.0 at 8 of 64, top-8), and the router at its published
width for every token. Not counted: the embedding look-up, softmax, norms,
rotary, the repeat of the key and value heads, the router's softmax and
top-k, sorts and gathers, the optimizer, and anything the program computes
twice.

Multiply-adds per token at the published widths (d 2304, 32 query heads over
4 key-value heads of 128, 8192 tokens, window 1024). Projections: W_q 2304 x
4096 = 9.44 M, W_k and W_v 2304 x 512 = 1.18 M each, W_o 4096 x 2304 = 9.44
M: 21.23 M. Attention: 2 x 32 x 128 x pairs / T = 7.86 M a windowed layer
(960.06 visible keys a query), 33.56 M the full one (4096.5). An expert
layer: router 0.15 M, routed 1.0 x 3 x 2304 x 896 = 6.19 M. The head, once,
2304 x 12288 = 28.31 M.
"""

# The held experts' grouped matmuls are counted as `qwen3_next_hybrid.py`
# counts them, by its function: nine products a layer (gate, up, down:
# forward, input gradient, weight gradient), each M x d x f multiply-adds with
# M = seq_len * top_k * experts_held / n_expert rows, the held experts'
# assignments under even routing (8192 a layer here); bytes: one M x d and
# one M x f activation a product, in bf16, not the held experts' stack.
from flops.qwen3_next_hybrid import share_expert_counts

KINDS = ("sliding_attention", "full_attention")
PERIOD = (KINDS[0],) * 3 + (KINDS[1],)      # the published `layer_types`


def visible_pairs(seq_len, window=None):
    """Pairs (query i, key j) with `j <= i` and, under a window, `i - j <
    window`."""
    t = seq_len
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def attention_counts(seq_len, n_layer, n_head, head_dim, window=None,
                     bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the attention kernels of one
    example, `n_layer` layers of one kind: seven `T x T` products a head
    (forward: scores, context; backward: scores again, dP, dV, dK, dQ), the
    visible pairs of each, whatever tiles compute them. Each layer reads or
    writes q, dq, Out, dOut and the REPEATED k, v, dk, dv (`n_head` heads
    each: the kernels see the key and value heads after `layers.expand`)
    `[T, heads x head_dim]` once, in bf16 under AMP (the rows' float32
    log-sum-exp is 1/64 of q and is left out)."""
    flops = n_layer * 7 * 2 * visible_pairs(seq_len, window) * head_dim \
        * n_head
    values = 8 * seq_len * n_head * head_dim
    return {"flops": flops, "bytes": n_layer * values * bytes_per_value}


def flops_per_example(seq_len, vocab_size=98304, n_layer=28, d_model=2304,
                      n_head=32, n_kv_head=4, head_dim=128, layer_types=PERIOD,
                      sliding_window=1024,
                      n_expert=64, top_k=8, d_expert=896, experts_held=None,
                      **_):
    t, d = seq_len, d_model
    held = n_expert if experts_held is None else experts_held
    kinds = [layer_types[i % len(layer_types)] for i in range(n_layer)]
    n_window, n_full = kinds.count(KINDS[0]), kinds.count(KINDS[1])
    per_token = {
        "projections": d * n_head * head_dim + 2 * d * n_kv_head * head_dim
        + n_head * head_dim * d,
        # QK^T and PV over the visible pairs, averaged over the queries
        "window_attention":
            2 * n_head * head_dim * visible_pairs(t, sliding_window) // t,
        "full_attention": 2 * n_head * head_dim * visible_pairs(t) // t,
        "router": d * n_expert,
        "routed_experts": top_k * held * 3 * d * d_expert // n_expert,
    }
    kernels = n_window * per_token["window_attention"] \
        + n_full * per_token["full_attention"]
    mixers = n_layer * per_token["projections"] + kernels
    experts = n_layer * (per_token["router"] + per_token["routed_experts"])
    head = d * vocab_size
    total = mixers + experts + head
    fwd = 2 * total * t
    windowed = attention_counts(t, n_window, n_head, head_dim, sliding_window)
    full = attention_counts(t, n_full, n_head, head_dim)
    share = share_expert_counts(seq_len, n_layer, d_model, n_expert, held,
                                top_k, d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"window_attention": n_window,
                       "full_attention": n_full},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "mixers_share": mixers / total,
            "attention_kernels_share": kernels / total,
            "experts_share": experts / total,
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
