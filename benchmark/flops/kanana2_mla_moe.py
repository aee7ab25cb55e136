"""Model FLOPs of one training example of ONE CHIP'S SHARE of the Kanana-2
decoder (the `deepseek_v3` family: latent attention in every layer, a leading
dense gated MLP, then a share of a sigmoid-routed expert layer beside two
shared experts), from the configuration's shapes alone, and the operations
and bytes of the attention kernels and of the held experts' grouped matmuls
for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, and the
backward pass as twice the forward. Experts: the assignments this chip's
`experts_held` of `n_expert` experts get under even routing, `top_k *
experts_held / n_expert` a token (0.75 at 16 of 128, top-6), the shared
experts and the router at its published width for every token. The causal
attention needs half of the score and context products, so half is counted,
whatever the kernel computes; its score products are `qk_nope_dim +
qk_rope_dim` wide and its value products `v_head_dim` wide. Not counted: the
embedding look-up, softmax, norms, rotary, the concatenations that assemble
q and k, the router's sigmoid and top-k, sorts and gathers, the bias update,
the optimizer, and anything the program computes twice.

Multiply-adds per token at the published widths (d 2048, 32 heads, 4096
tokens). MLA projections: W_q 2048 x 6144 = 12.58 M, W_kva 2048 x 576 = 1.18
M, W_kvb 512 x 8192 = 4.19 M, W_o 4096 x 2048 = 8.39 M: 26.35 M. Attention
(causal half): T x 32 x (192 + 128) / 2 = 20.97 M. The dense MLP 3 x 2048 x
6144 = 37.75 M. An expert layer: router 0.26 M, shared 3 x 2048 x 1536 =
9.44 M, routed 0.75 x 3 x 2048 x 768 = 3.54 M: 13.24 M. The head, once, 2048
x 16032 = 32.83 M.
"""

# The held experts' grouped matmuls are counted as `qwen3_next_hybrid.py`
# counts them, by its function: nine products a layer (gate, up, down:
# forward, input gradient, weight gradient), each M x d x f multiply-adds with
# M = seq_len * top_k * experts_held / n_expert rows, the held experts'
# assignments under even routing (3072 a layer here); bytes: one M x d and
# one M x f activation a product, in bf16, not the held experts' stack, which
# XLA keeps on the chip (that file has the measurement). Its `n_layer` is
# the number of expert layers.
from flops.qwen3_next_hybrid import share_expert_counts


def mla_attention_counts(seq_len, n_layer, n_head, qk_dim, v_head_dim,
                         bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the attention kernels of one
    example, all `n_layer` layers: seven `T x T` products a head (forward:
    scores, context; backward: scores again, dP, dV, dK, dQ), of which a
    causal mask needs half. The scores, dK and dQ are `qk_dim` wide (four
    products), context, dP and dV `v_head_dim` wide (three). Each layer reads
    or writes q, k, dq, dk `[T, heads x qk_dim]` and v, Out, dOut, dv `[T,
    heads x v_head_dim]` once, in bf16 under AMP (the rows' float32
    log-sum-exp is 1/96 of q and is left out)."""
    per_head = 4 * qk_dim + 3 * v_head_dim
    flops = n_layer * 2 * seq_len * seq_len * n_head * per_head // 2
    values = seq_len * n_head * (4 * qk_dim + 4 * v_head_dim)
    return {"flops": flops, "bytes": n_layer * values * bytes_per_value}


def flops_per_example(seq_len, vocab_size=128256, n_layer=48,
                      n_dense_layer=1, d_model=2048, d_dense=6144, n_head=32,
                      kv_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                      v_head_dim=128, n_expert=128, top_k=6, d_expert=768,
                      n_shared=2, experts_held=None, **_):
    t, d = seq_len, d_model
    held = n_expert if experts_held is None else experts_held
    qk_dim = qk_nope_dim + qk_rope_dim
    n_moe = n_layer - n_dense_layer
    per_token = {
        "mla_projections": d * n_head * qk_dim + d * (kv_rank + qk_rope_dim)
        + kv_rank * n_head * (qk_nope_dim + v_head_dim)
        + n_head * v_head_dim * d,
        # QK^T at qk_dim and PV at v_head_dim, causal half
        "attention": t * n_head * (qk_dim + v_head_dim) // 2,
        "dense_mlp": 3 * d * d_dense,
        "router": d * n_expert,
        "shared_experts": 3 * d * n_shared * d_expert,
        "routed_experts": top_k * held * 3 * d * d_expert // n_expert,
    }
    mixer = per_token["mla_projections"] + per_token["attention"]
    experts = per_token["router"] + per_token["shared_experts"] \
        + per_token["routed_experts"]
    head = d * vocab_size
    total = n_layer * mixer + n_dense_layer * per_token["dense_mlp"] \
        + n_moe * experts + head
    fwd = 2 * total * t
    attention = mla_attention_counts(seq_len, n_layer, n_head, qk_dim,
                                     v_head_dim)
    share = share_expert_counts(seq_len, n_moe, d_model, n_expert, held,
                                top_k, d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"dense": n_dense_layer, "moe": n_moe},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "mla_layers_share": n_layer * mixer / total,
            "attention_kernels_share":
                n_layer * per_token["attention"] / total,
            "dense_mlp_share":
                n_dense_layer * per_token["dense_mlp"] / total,
            "experts_share": n_moe * experts / total,
            "head_share": head / total,
            "mla_attention_flops": attention["flops"],
            "mla_attention_bytes": attention["bytes"],
            "share_expert_flops": share["flops"],
            "share_expert_bytes": share["bytes"],
            "share_expert_rows": share["rows"]}
