"""Model FLOPs of one training example of ONE CHIP'S SHARE of a run of
Ling-3.0-flash-VL's language-model layers (Kimi Delta Attention mixers, one
gated latent-attention layer a group of six, a leading dense MLP, a share of
a group-limited sigmoid-routed expert layer beside one shared expert), from
the configuration's shapes alone, and the operations and bytes of the
per-channel delta rule for its roofline share.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, the
convolution's taps, and the backward pass as twice the forward. Experts: the
assignments this chip's `experts_held` of `n_expert` experts get under even
routing, `top_k * experts_held / n_expert` a token (0.125 at 8 of 512,
top-8), the shared expert and the router at its published width for every
token. The causal attention needs half of the score and context products, so
half is counted. The rule is counted in its chunked form at chunk 64 and
block 16 (`kda_macs_per_token`): the form every implementation on a matrix
unit takes, its `[chunk, chunk]` tiles whole. Not counted: the embedding
look-up, softmax, norms, rotary, the gates' exponentials and sigmoids, silu,
the l2-norms, the router's sigmoid, group scores and top-k, sorts and
gathers, the bias update, the optimizer, and anything the program computes
twice.

Multiply-adds per token at the published widths (d 2560, 32 heads of 128;
2048 tokens). A KDA mixer: W_q, W_k, W_v, W_f, W_g, W_o 2560 x 4096 = 10.49 M
each, W_b 2560 x 32 = 0.08 M: 63.00 M; the convolution 12288 x 4 = 0.05 M;
the rule 32 x (2 x 64 x 128 + 2 x 64 x 128 + 64 x 128 + 3 x 128 x 128) =
2.88 M: 65.93 M. The MLA mixer: W_q 2560 x 6144 = 15.73 M, W_kva 2560 x 576 =
1.47 M, W_kvb 512 x 8192 = 4.19 M, W_o 4096 x 2560 = 10.49 M, the gate 0.08
M: 31.97 M, and attention (causal half) T x 32 x (192 + 128) / 2 = 10.49 M.
The dense MLP 3 x 2560 x 6144 = 47.19 M. An expert layer: router 2560 x 512 =
1.31 M, shared 3 x 2560 x 768 = 5.90 M, routed 0.125 x 3 x 2560 x 768 = 0.74
M: 7.95 M. The head, once, 2560 x 19648 = 50.30 M. Published layers 1-6:
5 x 65.93 + 42.46 + 47.19 + 5 x 7.95 + 50.30 = 509.3 M multiply-adds = 1019
MFLOP a token forward, 6.26 TFLOP a step of 2048 tokens forward and backward;
the KDA mixers 65%.
"""

from flops.kanana2_mla_moe import mla_attention_counts
from flops.qwen3_next_hybrid import share_expert_counts


def layer_counts(n_layer, first_layer, layer_group_size, n_dense_layer):
    """(KDA layers, MLA layers, dense layers, expert layers) of the run of
    `n_layer` published layers from `first_layer` on."""
    published = range(first_layer, first_layer + n_layer)
    mla = sum(1 for p in published if (p + 1) % layer_group_size == 0)
    dense = sum(1 for p in published if p < n_dense_layer)
    return n_layer - mla, mla, dense, n_layer - dense


def kda_macs_per_token(n_head, head_dim, chunk):
    """Multiply-adds a token of the chunked per-channel delta rule, forward,
    all heads, key and value heads `head_dim` wide. Per chunk of C tokens and
    head: the two Gram tiles `(beta k) k^T` and `q k^T` under their decays,
    C^2 Dk each (made in 16-row blocks: the same products); the solve's two
    right-hand sides `T [beta v | beta k exp(G)]`, C^2 (Dv + Dk); the scores
    times v', C^2 Dv; `w S`, `(q exp(G)) S` and the state's update
    `k_tail^T v'`, C Dk Dv each. Divided by C: C (2 Dk) + C (Dk + Dv) + C Dv
    + 3 Dk Dv."""
    dk = dv = head_dim
    per_chunk_token = chunk * (2 * dk) + chunk * (dk + dv) + chunk * dv \
        + 3 * dk * dv
    return n_head * per_chunk_token


def kda_counts(seq_len, kda_layers, n_head, head_dim, chunk,
               bytes_per_value=2):
    """`kda_flops` and `kda_bytes`: FLOPs and HBM bytes a step needs for the
    rule of one example, all KDA layers, forward and backward (twice the
    forward's products and traffic). Bytes a token and layer forward: q, k,
    v and o `[H Dh]` in bf16 under AMP, g `[H Dh]` float32 (16 KB), beta
    `[H]` float32, and the state each chunk starts from, `[H, Dh, Dh]`
    float32 once a chunk (what a backward reads back: 32 KB a token at the
    published shapes and chunk 64, of 80.1 KB in all)."""
    wide = n_head * head_dim
    flops = kda_layers * 3 * 2 * seq_len * kda_macs_per_token(
        n_head, head_dim, chunk)
    token = 4 * wide * bytes_per_value + wide * 4 + n_head * 4 \
        + n_head * head_dim * head_dim * 4 // chunk
    return {"flops": flops, "bytes": kda_layers * 3 * seq_len * token,
            "bytes_per_token_forward": token}


def parameters(vocab_size, n_layer, first_layer, layer_group_size,
               n_dense_layer, d_model, d_dense, n_head, head_dim, conv_kernel,
               kv_rank, qk_nope_dim, qk_rope_dim, v_head_dim, n_expert,
               d_expert, d_shared, experts_held):
    """The parameters this chip holds (the router's selection biases, not
    trained, left out): what the configuration's `deployment` states."""
    d, wide = d_model, n_head * head_dim
    kda, mla, dense, moe = layer_counts(n_layer, first_layer,
                                        layer_group_size, n_dense_layer)
    kda_mixer = 6 * d * wide + d * n_head + 3 * wide * conv_kernel \
        + n_head + wide + head_dim
    mla_mixer = d * n_head * (qk_nope_dim + qk_rope_dim) \
        + d * (kv_rank + qk_rope_dim) + kv_rank \
        + kv_rank * n_head * (qk_nope_dim + v_head_dim) \
        + n_head * v_head_dim * d + d * n_head
    held = n_expert if experts_held is None else experts_held
    experts = d * n_expert + 3 * d * d_shared + held * 3 * d * d_expert
    norms = 2 * d * n_layer + d
    return kda * kda_mixer + mla * mla_mixer + dense * 3 * d * d_dense \
        + moe * experts + norms + 2 * vocab_size * d


def flops_per_example(seq_len, vocab_size=157184, n_layer=42, first_layer=0,
                      layer_group_size=6, n_dense_layer=2, d_model=2560,
                      d_dense=6144, n_head=32, head_dim=128, conv_kernel=4,
                      chunk=64, kv_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                      v_head_dim=128, n_expert=512, top_k=8, d_expert=768,
                      d_shared=768, experts_held=None, **_):
    t, d, wide = seq_len, d_model, n_head * head_dim
    held = n_expert if experts_held is None else experts_held
    qk_dim = qk_nope_dim + qk_rope_dim
    kda, mla, dense, moe = layer_counts(n_layer, first_layer,
                                        layer_group_size, n_dense_layer)
    per_token = {
        "kda_projections": 6 * d * wide + d * n_head,
        "kda_convolution": 3 * wide * conv_kernel,
        "kda_rule": kda_macs_per_token(n_head, head_dim, chunk),
        "mla_projections": d * n_head * qk_dim + d * (kv_rank + qk_rope_dim)
        + kv_rank * n_head * (qk_nope_dim + v_head_dim)
        + n_head * v_head_dim * d + d * n_head,
        "attention": t * n_head * (qk_dim + v_head_dim) // 2,
        "dense_mlp": 3 * d * d_dense,
        "router": d * n_expert,
        "shared_expert": 3 * d * d_shared,
        "routed_experts": top_k * held * 3 * d * d_expert // n_expert,
    }
    kda_mixer = per_token["kda_projections"] + per_token["kda_convolution"] \
        + per_token["kda_rule"]
    mla_mixer = per_token["mla_projections"] + per_token["attention"]
    experts = per_token["router"] + per_token["shared_expert"] \
        + per_token["routed_experts"]
    head = d * vocab_size
    total = kda * kda_mixer + mla * mla_mixer \
        + dense * per_token["dense_mlp"] + moe * experts + head
    fwd = 2 * total * t
    rule = kda_counts(seq_len, kda, n_head, head_dim, chunk)
    attention = mla_attention_counts(seq_len, mla, n_head, qk_dim, v_head_dim)
    share = share_expert_counts(seq_len, moe, d_model, n_expert, held, top_k,
                                d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"kda": kda, "mla": mla, "dense": dense, "moe": moe},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "kda_layers_share": kda * kda_mixer / total,
            "mla_layers_share": mla * mla_mixer / total,
            "dense_mlp_share": dense * per_token["dense_mlp"] / total,
            "experts_share": moe * experts / total,
            "head_share": head / total,
            "kda_flops": rule["flops"], "kda_bytes": rule["bytes"],
            "kda_bytes_per_token_forward": rule["bytes_per_token_forward"],
            "mla_attention_flops": attention["flops"],
            "mla_attention_bytes": attention["bytes"],
            "share_expert_flops": share["flops"],
            "share_expert_bytes": share["bytes"],
            "share_expert_rows": share["rows"]}
