"""Model FLOPs of one training example of ONE CHIP'S SHARE of Keye-VL-2.0's
language model (every layer: grouped-head attention over a learned selection
of `topk` keys a query, DeepSeek-Sparse-Attention's indexer, then a share of a
renormalised top-k expert layer; no shared expert, no dense layer), from the
configuration's shapes alone, and the operations and bytes of the attention
kernels under a kept set, of the index-score kernel and of the held experts'
grouped matmuls for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, and the
backward pass as twice the forward, EXCEPT the indexer (its three
projections and its score products), which has no backward pass: no gradient
passes the selection. Attention: the KEPT pairs of query and key, whatever
tiles a kernel computes them in: row t keeps `min(t + 1, topk)` keys, so a
layer has `topk (topk + 1) / 2 + (T - topk) topk` pairs (14,681,088 at 8192 /
2048: 43.75% of the triangle's 33,558,528), each pair one multiply-add a head
dim in the score product and one in the context product. The index scores:
the CAUSAL pairs (every key below the diagonal is scored before any is
dropped), `n_index_head x index_dim` multiply-adds a pair. Experts: the
assignments this chip's `experts_held` of `n_expert` experts get under even
routing, `top_k * experts_held / n_expert` a token (0.5 at 8 of 128, top-8),
and the router at its published width for every token. Not counted: the
embedding look-up, softmax, the norms, rotary, the repeat of the key and value
heads, the ReLU and the head sum of the index scores, the selection (a
bisection: comparisons, no products), the router's softmax and top-k, sorts
and gathers, the optimizer, and anything the program computes twice (the
tiles of the causal triangle that hold keys which are not kept).

Multiply-adds per token at the published widths (d 2048, 32 query heads over
4 key-value heads of 128, 16 index heads of 64, 8192 tokens, topk 2048).
Projections: W_q and W_o 2048 x 4096 = 8.39 M each, W_k and W_v 2048 x 512 =
1.05 M each: 18.87 M. Indexer projections: 2048 x 1024 + 2048 x 64 + 2048 x
16 = 2.26 M. Attention: 2 x 32 x 128 x 14,681,088 / 8192 = 14.68 M (1792.1
kept keys a query). Index scores: 16 x 64 x 33,558,528 / 8192 = 4.19 M
(4096.5 keys a query). An expert layer: router 0.26 M, routed 0.5 x 3 x 2048 x
768 = 2.36 M. The head, once, 2048 x 18992 = 38.90 M.
"""

from flops.qwen3_next_hybrid import share_expert_counts


def kept_pairs(seq_len, topk):
    """Pairs (query t, key s) a selection of the `topk` largest of `s <= t`
    keeps: `min(t + 1, topk)` a row."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def attention_counts(seq_len, n_layer, n_head, head_dim, topk,
                     bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the attention kernels of one
    example under a kept set: seven `T x T` products a head (forward: scores,
    context; backward: scores again, dP, dV, dK, dQ) over the KEPT pairs of
    each, whatever tiles compute them. Bytes: q, dq, Out, dOut and the
    REPEATED k, v, dk, dv `[T, heads x head_dim]` once each in bf16, and the
    kept set, int8 `[T, T]`, once for the forward call and once for the
    backward (the calls read a tile of it a head again: not counted)."""
    flops = n_layer * 7 * 2 * kept_pairs(seq_len, topk) * head_dim * n_head
    values = 8 * seq_len * n_head * head_dim
    return {"flops": flops, "bytes": n_layer * (
        values * bytes_per_value + 2 * seq_len * seq_len)}


def index_counts(seq_len, n_layer, n_index_head, index_dim,
                 bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the index-score kernel of one
    example: one product of `index_dim` a causal pair an index head, forward
    only; bytes: the float32 scores written `[T, T]` (the half above the
    diagonal as minus infinity), the index queries, the key head and the
    weights read once."""
    flops = n_layer * 2 * causal_pairs(seq_len) * n_index_head * index_dim
    read = seq_len * (n_index_head * index_dim + index_dim + n_index_head)
    return {"flops": flops, "bytes": n_layer * (
        4 * seq_len * seq_len + read * bytes_per_value)}


def flops_per_example(seq_len, vocab_size=151936, n_layer=48, d_model=2048,
                      n_head=32, n_kv_head=4, head_dim=128, n_index_head=16,
                      index_dim=64, topk=2048, n_expert=128, top_k=8,
                      d_expert=768, experts_held=None, **_):
    t, d = seq_len, d_model
    held = n_expert if experts_held is None else experts_held
    per_token = {
        "projections": 2 * d * n_head * head_dim
        + 2 * d * n_kv_head * head_dim,
        "index_projections": d * (n_index_head * index_dim + index_dim
                                  + n_index_head),
        # QK^T and PV over the kept pairs, averaged over the queries
        "kept_attention": 2 * n_head * head_dim * kept_pairs(t, topk) // t,
        # one product a causal pair an index head
        "index_scores": n_index_head * index_dim * causal_pairs(t) // t,
        "router": d * n_expert,
        "routed_experts": top_k * held * 3 * d * d_expert // n_expert,
    }
    index = n_layer * (per_token["index_projections"]
                       + per_token["index_scores"])
    kernels = n_layer * per_token["kept_attention"]
    mixers = n_layer * per_token["projections"] + kernels
    experts = n_layer * (per_token["router"] + per_token["routed_experts"])
    head = d * vocab_size
    trained = mixers + experts + head
    fwd = 2 * (trained + index) * t
    attention = attention_counts(t, n_layer, n_head, head_dim, topk)
    scores = index_counts(t, n_layer, n_index_head, index_dim)
    share = share_expert_counts(seq_len, n_layer, d_model, n_expert, held,
                                top_k, d_expert)
    return {"forward": fwd,
            # the indexer runs forward only
            "forward_backward": 2 * (3 * trained + index) * t,
            "positions_per_example": t,
            "layers": {"sparse_attention": n_layer},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "mixers_share": mixers / (trained + index),
            "attention_kernels_share": kernels / (trained + index),
            "indexer_share": index / (trained + index),
            "experts_share": experts / (trained + index),
            "head_share": head / (trained + index),
            "kept_pairs": kept_pairs(t, topk),
            "causal_pairs": causal_pairs(t),
            "dsa_attention_flops": attention["flops"],
            "dsa_attention_bytes": attention["bytes"],
            "dsa_index_flops": scores["flops"],
            "dsa_index_bytes": scores["bytes"],
            "share_expert_flops": share["flops"],
            "share_expert_bytes": share["bytes"],
            "share_expert_rows": share["rows"]}
