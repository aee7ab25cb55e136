"""Model FLOPs of one training example of ONE CHIP'S SHARE of the Qwen3-Next
hybrid decoder (gated-delta-rule layers beside gated softmax attention, a
share of a sparse-expert layer with a shared expert), from the configuration's
shapes alone, and the operations and bytes of the delta rule and of the held
experts' grouped matmuls for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, and the
backward pass as twice the forward. Experts: the assignments this chip's
`experts_held` of `n_expert` experts get under even routing, `top_k *
experts_held / n_expert` a token (0.625 at 32 of 512, top-10: what the cell
routes to them within 8%, `share_expert_counts`), the shared
expert and the router at its published width for every token. The causal
attention needs half of the score and context products, so half is counted,
whatever the kernel computes. The delta rule is counted in its chunked form
at the public code's chunk of 64 (`gdn_counts`): the form every
implementation on a matrix unit takes. Not counted: the embedding look-up,
softmax, norms, rotary, the convolution (4 multiply-adds a channel), the
gates, the router's top-k, sorts and gathers, the optimizer, and anything the
program computes twice.

Multiply-adds per token at the published widths (d 2048; 4096 tokens). A
delta-rule layer: W_qkvz 2048 x 12288 = 25.17 M, W_ba 0.13 M, W_out 8.39 M,
the rule 2.62 M: 36.31 M. The attention layer: W_q 2048 x 8192 = 16.78 M,
W_k and W_v 1.05 M each, W_o 8.39 M, attention (causal half) T x 16 x 256 =
16.78 M: 44.04 M. Every layer's experts: router 1.05 M, shared 3.15 M, routed
0.625 x 3.15 M = 1.97 M: 6.16 M. The head, once, 2048 x 18992 = 38.90 M.
"""

CHUNK = 64          # the public code's chunk_size


def layer_kinds(n_layer, full_attention_interval):
    """(delta-rule layers, full-attention layers)."""
    full = sum((i + 1) % full_attention_interval == 0 for i in range(n_layer))
    return n_layer - full, full


def gdn_rule_macs_per_token(n_value_head, key_dim, value_dim, chunk=CHUNK):
    """Multiply-adds a token of the chunked gated delta rule, forward, all
    value heads. Per head and chunk of C tokens: (beta k) k^T and q k^T, C^2
    Dk each; the unit-lower-triangular solve of [beta v | beta k exp(G)],
    C^2 (Dk + Dv) / 2; w S, k^T v' and q S, C Dk Dv each; scores v', C^2
    Dv. Divided by C: 3 Dk Dv + C (2 Dk + Dv) + C (Dk + Dv) / 2."""
    per_head = 3 * key_dim * value_dim + chunk * (2 * key_dim + value_dim) \
        + chunk * (key_dim + value_dim) // 2
    return n_value_head * per_head


def gdn_counts(seq_len, n_layer, full_attention_interval, n_key_head,
               n_value_head, key_dim, value_dim, bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the delta rule of one example,
    all delta-rule layers, forward and backward (twice the forward's
    products). Bytes: forward reads q, k `[T, key_heads x key_dim]` and v
    and writes o `[T, value_heads x value_dim]`; backward reads q, k, v and
    dO and writes dq, dk, dv; all in bf16 under AMP (g and beta, float32
    `[T, value_heads]`, are 1/64 of v and are left out, as is any state
    kept between the passes, which an implementation may keep or not)."""
    layers, _ = layer_kinds(n_layer, full_attention_interval)
    flops = layers * 3 * 2 * seq_len * gdn_rule_macs_per_token(
        n_value_head, key_dim, value_dim)
    qk = 2 * seq_len * n_key_head * key_dim
    v = seq_len * n_value_head * value_dim
    values = (qk + 2 * v) + (qk + 2 * v) + (qk + v)
    return {"flops": flops, "bytes": layers * values * bytes_per_value}


def share_expert_counts(seq_len, n_layer, d_model, n_expert, experts_held,
                        top_k, d_expert, bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the grouped matmuls of the held
    experts of one example: three projections (gate, up, down), each computed
    three times (forward, input gradient, weight gradient). Every one of the
    nine is M x d x f multiply-adds with M = seq_len * top_k * experts_held /
    n_expert rows: the held experts' assignments under even routing (2560 a
    layer). The cell's step sees 2440-2751 a layer through a whole run, in
    32-33 row tiles (chip run, PR 34, call 6; the configuration's
    `assumed.optimizer` says what holds it there).
    Bytes: one M x d and one M x f activation a product, in bf16. NOT the
    held experts' stack (experts_held x d x f, 67 MB in bf16): XLA keeps a
    whole stack on the chip (memory space S(1) in the instruction's layout:
    the cast from the float32 weights writes it there, the weight gradient is
    written there for Adam to read), so a product's time is not bounded by
    the stack's way through HBM. Measured: 36 calls in 2.96 ms at ~2700
    rows a layer, 1.3-1.7 TB/s of operand bytes where HBM gives 0.82; with
    the stacks counted the share read 119% (chip run, PR 34, call 5)."""
    rows = seq_len * top_k * experts_held // n_expert
    products = 9 * n_layer
    flops = products * 2 * rows * d_model * d_expert
    values = rows * d_model + rows * d_expert
    return {"flops": flops, "bytes": products * values * bytes_per_value,
            "rows": rows}


def flops_per_example(seq_len, vocab_size=151936, n_layer=48, d_model=2048,
                      full_attention_interval=4, n_head=16, n_kv_head=2,
                      head_dim=256, n_key_head=16, n_value_head=32,
                      key_dim=128, value_dim=128, n_expert=512, top_k=10,
                      d_expert=512, d_shared=512, experts_held=None, **_):
    t, d = seq_len, d_model
    held = n_expert if experts_held is None else experts_held
    linear, full = layer_kinds(n_layer, full_attention_interval)
    r = n_value_head // n_key_head
    per_token = {
        "gdn_projections": d * n_key_head * (2 * key_dim + 2 * r * value_dim)
        + d * n_key_head * 2 * r + n_value_head * value_dim * d,
        "gdn_rule": gdn_rule_macs_per_token(n_value_head, key_dim, value_dim),
        "attention_projections": d * n_head * 2 * head_dim
        + 2 * d * n_kv_head * head_dim + n_head * head_dim * d,
        "attention": t * n_head * head_dim,     # QK^T and PV, causal half
        "router": d * n_expert,
        "shared_expert": 3 * d * d_shared + d,
        "routed_experts": top_k * held * 3 * d * d_expert // n_expert,
    }
    gdn_layer = per_token["gdn_projections"] + per_token["gdn_rule"]
    attn_layer = per_token["attention_projections"] + per_token["attention"]
    experts = per_token["router"] + per_token["shared_expert"] \
        + per_token["routed_experts"]
    head = d * vocab_size
    total = linear * gdn_layer + full * attn_layer + n_layer * experts + head
    fwd = 2 * total * t
    gdn = gdn_counts(seq_len, n_layer, full_attention_interval, n_key_head,
                     n_value_head, key_dim, value_dim)
    share = share_expert_counts(seq_len, n_layer, d_model, n_expert, held,
                                top_k, d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"linear_attention": linear, "full_attention": full},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "gdn_layers_share": linear * gdn_layer / total,
            "attention_layers_share": full * attn_layer / total,
            "experts_share": n_layer * experts / total,
            "head_share": head / total,
            "gdn_flops": gdn["flops"], "gdn_bytes": gdn["bytes"],
            "share_expert_flops": share["flops"],
            "share_expert_bytes": share["bytes"],
            "share_expert_rows": share["rows"]}
