"""Model FLOPs of one training example of ONE CHIP'S SHARE of the LFM2-MoE
decoder (`model_type: lfm2_moe`: gated short convolutions beside QK-normed
rotary attention, a leading dense gated MLP, then a share of a sigmoid-routed
expert layer with NO shared expert, one table as embedding and head), from
the configuration's shapes alone, and the operations and bytes of the gated
short-convolution operator, of the attention kernels and of the held experts'
grouped matmuls for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, the
convolution's taps, and the backward pass as twice the forward. The causal
attention needs the visible pairs of query and key (`mellum2_swa_moe.py::
visible_pairs`: `T (T + 1) / 2`), each one multiply-add a head dim in the
score product and one in the context product, whatever tiles a kernel
computes. Experts: the assignments this chip's `experts_held` of `n_expert`
experts get under even routing, `top_k * experts_held / n_expert` a token (1
at 8 of 32, top-4), and the router at its published width for every token.
The tied table is counted once, as the head's product over the held rows (the
look-up is no product). Not counted: the gates' two products a channel,
softmax, the norms, rotary, the repeat of the key and value heads, the
router's sigmoid and top-k, sorts and gathers, the bias update, the
optimizer, and anything the program computes twice.

Multiply-adds per token at the published widths (d 2048, 4096 tokens). A conv
operator: W_in 2048 x 6144 = 12.58 M, W_out 2048 x 2048 = 4.19 M, the taps
2048 x 3 = 0.006 M: 16.78 M. The attention operator: W_q and W_o 2048 x 2048
each, W_k and W_v 2048 x 512 each = 10.49 M, attention 2 x 32 x 64 x 2048.5 =
8.39 M: 18.88 M. The dense MLP 3 x 2048 x 7168 = 44.04 M. An expert layer:
router 2048 x 32 = 0.07 M, routed 1 x 3 x 2048 x 1792 = 11.01 M: 11.08 M. The
head, once, 2048 x 16384 = 33.55 M. The cut (layers 1-5: four conv operators,
one attention, one dense MLP, four expert layers) and the head: 4 x 16.78 +
18.88 + 44.04 + 4 x 11.08 + 33.55 = 207.9 M multiply-adds = 415.8 MFLOP a
token forward, 5.11 TFLOP a step of 4096 tokens forward and backward: the
conv operators 32%, the held experts and routers 21%, the dense MLP 21%, the
head 16%, attention 9%.
"""

from flops.mellum2_swa_moe import attention_counts, visible_pairs
from flops.qwen3_next_hybrid import share_expert_counts

KINDS = ("conv", "full_attention")
# as published: attention at 2, 6, 10, 14, 18, 21 of 24
PUBLISHED = ["full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
             for i in range(24)]


def layer_counts(layer_types, first_layer, n_dense_layer):
    """(conv layers, attention layers, dense layers, expert layers) of the
    published layers `first_layer ..` with the kinds `layer_types`."""
    kinds = list(layer_types)
    dense = sum(1 for i in range(len(kinds)) if first_layer + i < n_dense_layer)
    return kinds.count(KINDS[0]), kinds.count(KINDS[1]), dense, \
        len(kinds) - dense


def short_conv_cost(seq_len, layer_types=PUBLISHED, d_model=2048, conv_taps=3,
                    bytes_per_value=2, **_):
    """`flops` and `bytes`: FLOPs and HBM bytes a step needs for the gated
    short-convolution OPERATOR of one example, all conv layers, forward and
    backward, from the residual stream back to it (its norm, both
    projections, the two gates, the convolution, the residual add): the work
    the operator needs, whatever implements it and however it is cut into
    kernels and fusions. Not the part "between the projections" alone: XLA
    fuses the slices and a gate into the projections' own fusions, so no
    set of instructions holds that part and nothing else (PR 69: the ops it
    owns there read 0.73 ms a step where 11 d values a token would need
    0.90). FLOPs a token and layer forward: the projections' and the taps'
    multiply-adds at 2 (4 d^2 + K d) and the gates' two products a channel;
    the backward twice that. Bytes a token and layer, in the stream's dtype
    (bf16 under AMP): the stream read and written forward (2 d), read with
    its gradient and the gradient written backward (3 d): what one fused
    pass would move; and a layer's weights a step: the two projections in
    bf16 read once forward and twice backward, their float32 gradients
    written once, the taps' float32 weight read twice and its gradient
    written. Bound by compute at 4096 tokens: 8.38 ms a step of four layers
    at 197 TFLOP/s, 1.23 at 819 GB/s."""
    n_conv = list(layer_types).count(KINDS[0])
    d, k = d_model, conv_taps
    macs = 4 * d * d + k * d
    token_bytes = 5 * d * bytes_per_value
    weight_bytes = 4 * d * d * (3 * bytes_per_value + 4) + 3 * d * k * 4
    return {"flops": n_conv * seq_len * 3 * (2 * macs + 2 * d),
            "bytes": n_conv * (seq_len * token_bytes + weight_bytes),
            "bytes_per_token_and_layer": token_bytes}


def parameters(vocab_size, layer_types, first_layer, n_dense_layer, d_model,
               d_dense, conv_taps, n_head, n_kv_head, head_dim, n_expert,
               d_expert, experts_held, tie_embeddings=True):
    """The parameters this chip holds (the router biases, which are not
    trained, among them): what the configuration's `deployment` states."""
    d = d_model
    n_conv, n_attn, n_dense, n_moe = layer_counts(layer_types, first_layer,
                                                  n_dense_layer)
    conv = d * 3 * d + d * d + d * conv_taps
    attention = 2 * d * n_head * head_dim + 2 * d * n_kv_head * head_dim \
        + 2 * head_dim
    expert_layer = d * n_expert + n_expert + experts_held * 3 * d * d_expert
    return n_conv * conv + n_attn * attention + n_dense * 3 * d * d_dense \
        + n_moe * expert_layer + (n_conv + n_attn) * 2 * d + d \
        + (1 if tie_embeddings else 2) * vocab_size * d


def flops_per_example(seq_len, vocab_size=65536, layer_types=PUBLISHED,
                      first_layer=0, n_dense_layer=2, d_model=2048,
                      d_dense=7168, conv_taps=3, n_head=32, n_kv_head=8,
                      head_dim=64, n_expert=32, top_k=4, d_expert=1792,
                      experts_held=None, tie_embeddings=True, **_):
    t, d = seq_len, d_model
    held = n_expert if experts_held is None else experts_held
    n_conv, n_attn, n_dense, n_moe = layer_counts(layer_types, first_layer,
                                                  n_dense_layer)
    per_token = {
        "conv_projections": d * 3 * d + d * d,
        "conv_taps": d * conv_taps,
        "attention_projections": 2 * d * n_head * head_dim
        + 2 * d * n_kv_head * head_dim,
        # QK^T and PV over the visible pairs, averaged over the queries
        "attention": 2 * n_head * head_dim * visible_pairs(t) // t,
        "dense_mlp": 3 * d * d_dense,
        "router": d * n_expert,
        "routed_experts": top_k * held * 3 * d * d_expert // n_expert,
    }
    conv = per_token["conv_projections"] + per_token["conv_taps"]
    attention = per_token["attention_projections"] + per_token["attention"]
    experts = per_token["router"] + per_token["routed_experts"]
    head = d * vocab_size
    total = n_conv * conv + n_attn * attention \
        + n_dense * per_token["dense_mlp"] + n_moe * experts + head
    fwd = 2 * total * t
    short = short_conv_cost(seq_len, layer_types, d_model, conv_taps)
    full = attention_counts(t, n_attn, n_head, head_dim)
    share = share_expert_counts(seq_len, n_moe, d_model, n_expert, held,
                                top_k, d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"short_conv": n_conv, "full_attention": n_attn,
                       "dense": n_dense, "moe": n_moe},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "conv_operators_share": n_conv * conv / total,
            "attention_operators_share": n_attn * attention / total,
            "dense_mlp_share": n_dense * per_token["dense_mlp"] / total,
            "experts_share": n_moe * experts / total,
            "head_share": head / total,
            "parameters": parameters(vocab_size, layer_types, first_layer,
                                     n_dense_layer, d, d_dense, conv_taps,
                                     n_head, n_kv_head, head_dim, n_expert,
                                     d_expert, held, tie_embeddings),
            "short_conv_flops": short["flops"],
            "short_conv_bytes": short["bytes"],
            "short_conv_bytes_per_token_and_layer":
                short["bytes_per_token_and_layer"],
            "full_attention_flops": full["flops"],
            "full_attention_bytes": full["bytes"],
            "share_expert_flops": share["flops"],
            "share_expert_bytes": share["bytes"],
            "share_expert_rows": share["rows"]}
