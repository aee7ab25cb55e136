"""Model FLOPs of one training example of ONE CHIP'S SHARE of the Olmo-Hybrid
decoder (gated-delta-rule layers beside whole-projection QK-norm attention,
`heads_held` of `n_head` heads of every mixer, a dense gated feed-forward
held whole), from the configuration's shapes alone, and the operations and
bytes of the delta rule for its roofline share.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, and the
backward pass as twice the forward. The causal attention needs half of the
score and context products, so half is counted, whatever the kernel
computes. The delta rule is counted in its chunked form at chunk 64
(`qwen3_next_hybrid.gdn_rule_macs_per_token`, the sibling's: 3 Dk Dv + C (2
Dk + Dv) + C (Dk + Dv) / 2 multiply-adds a token and head): the form every
implementation on a matrix unit takes, whatever implements it here. Not counted: the embedding look-up,
softmax, norms, the convolution (4 multiply-adds a channel), the gates, the
optimizer, and anything the program computes twice.

Multiply-adds per token at the published widths under the share (d 3840, 15
of 30 heads, 4096 tokens). A delta-rule layer: W_q and W_k 3840 x 1440 each,
W_v and W_g 3840 x 2880 each = 33.18 M, W_o 11.06 M, W_a and W_b 0.12 M, the
rule 1.34 M (15 heads x (3 x 96 x 192 + 64 x 384 + 32 x 288)): 45.69 M. The
attention layer: four of 3840 x 1920 = 29.49 M, attention (causal half)
4096 x 15 x 128 = 7.86 M: 37.36 M. Every layer's feed-forward 3 x 3840 x
11008 = 126.81 M. The head, once, 3840 x 12544 = 48.17 M. One period and the
head: 3 x 45.69 + 37.36 + 4 x 126.81 + 48.17 = 729.9 M, 1460 MFLOP a token
forward, 17.9 TFLOP a step.
"""

from flops.qwen3_next_hybrid import CHUNK, gdn_rule_macs_per_token

PERIOD = ("linear_attention",) * 3 + ("full_attention",)


def layer_counts(n_layer, layer_types=PERIOD):
    """(delta-rule layers, full-attention layers)."""
    kinds = [layer_types[i % len(layer_types)] for i in range(n_layer)]
    full = kinds.count("full_attention")
    return n_layer - full, full


def gdn_counts(seq_len, gdn_layers, heads, key_dim, value_dim, chunk=CHUNK,
               bytes_per_value=2):
    """`gdn_flops` and `gdn_bytes`: FLOPs and HBM bytes a step needs for the
    rule of one example, all delta-rule layers, forward and backward (twice
    the forward's products and traffic), whatever implements it. Bytes a
    token and layer forward: q, k `[H Dk]`, v and o `[H Dv]` in bf16 under
    AMP, g and beta `[H]` float32, and the state each chunk starts from,
    `[H, Dk, Dv]` float32 once a chunk (what a backward reads back: 17.3 KB
    a token at 15 heads of 96 / 192 and chunk 64, of 34.7 KB in all)."""
    flops = gdn_layers * 3 * 2 * seq_len * gdn_rule_macs_per_token(
        heads, key_dim, value_dim, chunk)
    token = 2 * heads * (key_dim + value_dim) * bytes_per_value \
        + 2 * heads * 4 + heads * key_dim * value_dim * 4 // chunk
    return {"flops": flops, "bytes": gdn_layers * 3 * seq_len * token,
            "bytes_per_token_forward": token}


def parameters(vocab_size, n_layer, layer_types, d_model, d_ff, heads,
               head_dim, key_dim, value_dim, conv_kernel):
    """The parameters this chip holds: what the configuration's `deployment`
    states."""
    d = d_model
    gdn, full = layer_counts(n_layer, layer_types)
    wide_k, wide_v = heads * key_dim, heads * value_dim
    gdn_mixer = d * (2 * wide_k + 2 * wide_v) + wide_v * d + 2 * d * heads \
        + (2 * wide_k + wide_v) * conv_kernel + 2 * heads + value_dim
    attn_mixer = 4 * d * heads * head_dim + 2 * heads * head_dim
    return gdn * gdn_mixer + full * attn_mixer \
        + n_layer * (3 * d * d_ff + 2 * d) + d + 2 * vocab_size * d


def flops_per_example(seq_len, vocab_size=100352, n_layer=32,
                      layer_types=PERIOD, d_model=3840, d_ff=11008, n_head=30,
                      heads_held=None, head_dim=128, key_dim=96,
                      value_dim=192, conv_kernel=4, **_):
    t, d = seq_len, d_model
    heads = n_head if heads_held is None else heads_held
    gdn, full = layer_counts(n_layer, layer_types)
    wide_k, wide_v = heads * key_dim, heads * value_dim
    per_token = {
        "gdn_projections": d * (2 * wide_k + 2 * wide_v) + wide_v * d
        + 2 * d * heads,
        "gdn_rule": gdn_rule_macs_per_token(heads, key_dim, value_dim),
        "attention_projections": 4 * d * heads * head_dim,
        "attention": t * heads * head_dim,      # QK^T and PV, causal half
        "mlp": 3 * d * d_ff,
    }
    gdn_mixer = per_token["gdn_projections"] + per_token["gdn_rule"]
    attn_mixer = per_token["attention_projections"] + per_token["attention"]
    head = d * vocab_size
    total = gdn * gdn_mixer + full * attn_mixer + n_layer * per_token["mlp"] \
        + head
    fwd = 2 * total * t
    rule = gdn_counts(seq_len, gdn, heads, key_dim, value_dim)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"linear_attention": gdn, "full_attention": full},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "gdn_layers_share": gdn * gdn_mixer / total,
            "attention_layers_share": full * attn_mixer / total,
            "mlp_share": n_layer * per_token["mlp"] / total,
            "head_share": head / total,
            "parameters": parameters(vocab_size, n_layer, layer_types, d,
                                     d_ff, heads, head_dim, key_dim,
                                     value_dim, conv_kernel),
            "gdn_flops": rule["flops"], "gdn_bytes": rule["bytes"],
            "gdn_bytes_per_token_forward": rule["bytes_per_token_forward"]}
