"""Model FLOPs of one training example of ONE CHIP'S SHARE of the Nemotron-H
hybrid decoder (layers that are one sublayer each by a pattern string: Mamba-2
state-space mixers, softmax attention, a share of a sparse-expert layer of
two-matrix experts beside a shared expert), from the configuration's shapes
alone, and the operations and bytes of the selective scan and of the held
experts' grouped matmuls for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, the convolution's
taps, and the backward pass as twice the forward. Experts: the assignments
this chip's `experts_held` of `n_expert` experts get under even routing,
`top_k * experts_held / n_expert` a token (0.375 at 8 of 128, top-6), the
shared expert and the router at its published width for every token. The
causal attention needs half of the score and context products, so half is
counted, whatever the kernel computes. The scan is counted in its chunked
form at the published chunk of 128 (`ssd_macs_per_token`): the form every
implementation on a matrix unit takes, its `[chunk, chunk]` tiles whole (the
masked half of a tile is computed by every such implementation). Not counted:
the embedding look-up, softmax, norms, the gates' softplus and exponentials,
silu, relu², the router's top-k, sorts and gathers, the optimizer, and
anything the program computes twice.

Multiply-adds per token at the published widths (d 2688; 2048 tokens). An M
layer: W_in 2688 x 10304 = 27.70 M, W_out 4096 x 2688 = 11.01 M, the
convolution 6144 x 4 = 0.02 M, the scan 1.70 M: 40.44 M. The attention layer:
W_q 2688 x 4096 = 11.01 M, W_k and W_v 0.69 M each, W_o 11.01 M, attention
(causal half) T x 32 x 128 = 8.39 M: 31.79 M. An E layer: router 0.34 M,
shared 2 x 2688 x 3712 = 19.96 M, routed 0.375 x 2 x 2688 x 1856 = 3.74 M:
24.04 M. The head, once, 2688 x 16384 = 44.04 M. MEMEM*EME: 4 x 40.44 + 31.79
+ 4 x 24.04 + 44.04 = 333.8 M multiply-adds = 667.5 MFLOP a token forward,
4.10 TFLOP a step of 2048 tokens forward and backward; the M layers 48%.
"""


def layer_counts(layer_pattern):
    """(M layers, E layers, attention layers) of a pattern string."""
    return tuple(layer_pattern.count(k) for k in "ME*")


def ssd_macs_per_token(mamba_heads, mamba_head_dim, n_groups, ssm_state,
                       chunk):
    """Multiply-adds a token of the chunked selective scan, forward, all
    heads. Per chunk of C tokens: `C B^T`, C^2 N a GROUP (its heads share
    it); per head the decayed tile times x, C^2 P; `C S^T` (the state's part
    of y) and `(x w)^T B` (the state's update), C N P each. Divided by C:
    G C N + H (C P + 2 N P)."""
    return n_groups * chunk * ssm_state \
        + mamba_heads * (chunk * mamba_head_dim
                         + 2 * ssm_state * mamba_head_dim)


def ssd_counts(seq_len, layer_pattern, mamba_heads, mamba_head_dim, n_groups,
               ssm_state, chunk, bytes_per_value=2):
    """`ssd_flops` and `ssd_bytes`: FLOPs and HBM bytes a step needs for the
    selective scan of one example, all M layers, forward and backward (twice
    the forward's products and traffic). Bytes a token and layer forward: x
    and y `[H P]` and B, C `[G N]` in bf16 under AMP, dt and a `[H]` float32,
    and the state each chunk starts from, `[H, P, N]` float32 once a chunk
    (what the backward reads back: 16 KB a token at the published shapes, of
    37.4 KB in all)."""
    m_layers = layer_counts(layer_pattern)[0]
    inner, bc = mamba_heads * mamba_head_dim, n_groups * ssm_state
    flops = m_layers * 3 * 2 * seq_len * ssd_macs_per_token(
        mamba_heads, mamba_head_dim, n_groups, ssm_state, chunk)
    token = (2 * inner + 2 * bc) * bytes_per_value + 2 * mamba_heads * 4 \
        + mamba_heads * mamba_head_dim * ssm_state * 4 // chunk
    return {"flops": flops, "bytes": m_layers * 3 * seq_len * token,
            "bytes_per_token_forward": token}


def share_expert_counts(seq_len, layer_pattern, d_model, n_expert,
                        experts_held, top_k, d_expert, bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the grouped matmuls of the held
    two-matrix experts of one example: two projections (up, down), each
    computed three times (forward, input gradient, weight gradient). Every
    one of the six is M x d x f multiply-adds with M = seq_len * top_k *
    experts_held / n_expert rows: the held experts' assignments under even
    routing (768 a layer at 2048 tokens). Bytes: one M x d and one M x f
    activation a product, in bf16; not the held experts' stacks
    (`qwen3_next_hybrid.py::share_expert_counts` says why)."""
    rows = seq_len * top_k * experts_held // n_expert
    products = 6 * layer_counts(layer_pattern)[1]
    flops = products * 2 * rows * d_model * d_expert
    values = rows * d_model + rows * d_expert
    return {"flops": flops, "bytes": products * values * bytes_per_value,
            "rows": rows}


def flops_per_example(seq_len, vocab_size=131072, layer_pattern="MEMEM*EME",
                      d_model=2688, mamba_heads=64, mamba_head_dim=64,
                      n_groups=8, ssm_state=128, conv_kernel=4, chunk=128,
                      n_head=32, n_kv_head=2, head_dim=128, n_expert=128,
                      top_k=6, d_expert=1856, d_shared=3712,
                      experts_held=None, **_):
    t, d = seq_len, d_model
    held = n_expert if experts_held is None else experts_held
    m_layers, e_layers, full = layer_counts(layer_pattern)
    inner, bc = mamba_heads * mamba_head_dim, n_groups * ssm_state
    per_token = {
        "mamba_projections": d * (2 * inner + 2 * bc + mamba_heads)
        + inner * d,
        "mamba_convolution": (inner + 2 * bc) * conv_kernel,
        "mamba_scan": ssd_macs_per_token(mamba_heads, mamba_head_dim,
                                         n_groups, ssm_state, chunk),
        "attention_projections": 2 * d * n_head * head_dim
        + 2 * d * n_kv_head * head_dim,
        "attention": t * n_head * head_dim,     # QK^T and PV, causal half
        "router": d * n_expert,
        "shared_expert": 2 * d * d_shared,
        "routed_experts": top_k * held * 2 * d * d_expert // n_expert,
    }
    m_layer = per_token["mamba_projections"] \
        + per_token["mamba_convolution"] + per_token["mamba_scan"]
    attn_layer = per_token["attention_projections"] + per_token["attention"]
    experts = per_token["router"] + per_token["shared_expert"] \
        + per_token["routed_experts"]
    head = d * vocab_size
    total = m_layers * m_layer + full * attn_layer + e_layers * experts + head
    fwd = 2 * total * t
    ssd = ssd_counts(seq_len, layer_pattern, mamba_heads, mamba_head_dim,
                     n_groups, ssm_state, chunk)
    share = share_expert_counts(seq_len, layer_pattern, d_model, n_expert,
                                held, top_k, d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"state_space": m_layers, "experts": e_layers,
                       "full_attention": full},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "mamba_layers_share": m_layers * m_layer / total,
            "attention_layers_share": full * attn_layer / total,
            "experts_share": e_layers * experts / total,
            "head_share": head / total,
            "ssd_flops": ssd["flops"], "ssd_bytes": ssd["bytes"],
            "ssd_bytes_per_token_forward": ssd["bytes_per_token_forward"],
            "share_expert_flops": share["flops"],
            "share_expert_bytes": share["bytes"],
            "share_expert_rows": share["rows"]}
