"""Model FLOPs of one training example of the Granite 4.0-H hybrid decoder
(every layer a mixer AND a dense gated feed-forward; the mixer a Mamba-2
state-space mixer of ONE group of heads or grouped softmax attention without
positions; one table as embedding and head), from the configuration's shapes
alone, and the operations and bytes of the selective scan for its roofline
share.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, the
convolution's taps, and the backward pass as twice the forward. The causal
attention needs half of the score and context products, so half is counted,
whatever the kernel computes. The scan is counted in its chunked form at the
published chunk of 256 (`nemotron_h_hybrid.ssd_macs_per_token`, the sibling's:
G C N + H (C P + 2 N P) multiply-adds a token): the form every implementation
on a matrix unit takes, its `[chunk, chunk]` tiles whole (the masked half of
a tile is computed by every such implementation), `C B^T` once a GROUP: once
for all 64 heads here. The tied table is counted once, as the head's product
(the look-up is no product). Not counted: softmax, norms, the gates' softplus
and exponentials, silu, the four scalar multipliers, the optimizer, and
anything the program computes twice.

Multiply-adds per token at the published widths (d 2048; 2048 tokens). A
Mamba mixer: W_in 2048 x 8512 = 17.43 M, W_out 4096 x 2048 = 8.39 M, the
convolution 4352 x 4 = 0.02 M, the scan 2.13 M (1 x 256 x 128 + 64 x (256 x
64 + 2 x 128 x 64)): 27.97 M. The attention mixer: W_q and W_o 2048 x 2048
each, W_k and W_v 2048 x 512 each = 10.49 M, attention (causal half) T x 32 x
64 = 4.19 M: 14.68 M. Every layer's feed-forward 3 x 2048 x 8192 = 50.33 M.
The head, once, 2048 x 12544 = 25.69 M. Ten layers (9 + 1) and the head: 9 x
27.97 + 14.68 + 10 x 50.33 + 25.69 = 795.4 M multiply-adds = 1591 MFLOP a
token forward, 9.77 TFLOP a step of 2048 tokens forward and backward: the
feed-forwards 63%, the Mamba mixers 32% (their scans 2.4%), attention 1.8%,
the head 3.2%.
"""

from flops.nemotron_h_hybrid import ssd_macs_per_token

KINDS = ("mamba", "attention")


def layer_counts(layer_types):
    """(Mamba layers, attention layers) of a `layer_types` list."""
    return tuple(list(layer_types).count(k) for k in KINDS)


def ssd_counts(seq_len, layer_types, mamba_heads, mamba_head_dim, n_groups,
               ssm_state, chunk, bytes_per_value=2):
    """`ssd_flops` and `ssd_bytes`: FLOPs and HBM bytes a step needs for the
    selective scan of one example, all Mamba layers, forward and backward
    (twice the forward's products and traffic): the work the RULE needs,
    whatever implements it. Bytes a token and layer forward: x and y `[H P]`
    and B, C `[G N]` in bf16 under AMP, dt and a `[H]` float32, and the state
    each chunk hands on, `[H, P, N]` float32 once a chunk (8 KB a token at 64
    heads of 64 x 128 and chunk 256, of 25.1 KB in all)."""
    m_layers = layer_counts(layer_types)[0]
    inner, bc = mamba_heads * mamba_head_dim, n_groups * ssm_state
    flops = m_layers * 3 * 2 * seq_len * ssd_macs_per_token(
        mamba_heads, mamba_head_dim, n_groups, ssm_state, chunk)
    token = (2 * inner + 2 * bc) * bytes_per_value + 2 * mamba_heads * 4 \
        + mamba_heads * mamba_head_dim * ssm_state * 4 // chunk
    return {"flops": flops, "bytes": m_layers * 3 * seq_len * token,
            "bytes_per_token_forward": token}


def parameters(vocab_size, layer_types, d_model, d_ff, mamba_heads,
               mamba_head_dim, n_groups, ssm_state, conv_kernel, n_head,
               n_kv_head, head_dim, tie_embeddings=True):
    """The parameters this chip holds: what the configuration's `deployment`
    states."""
    d = d_model
    m_layers, full = layer_counts(layer_types)
    inner, bc = mamba_heads * mamba_head_dim, n_groups * ssm_state
    mamba = d * (2 * inner + 2 * bc + mamba_heads) + inner * d \
        + (inner + 2 * bc) * (conv_kernel + 1) + 3 * mamba_heads + inner
    attention = 2 * d * n_head * head_dim + 2 * d * n_kv_head * head_dim
    return m_layers * mamba + full * attention \
        + (m_layers + full) * (3 * d * d_ff + 2 * d) + d \
        + (1 if tie_embeddings else 2) * vocab_size * d


def flops_per_example(seq_len, vocab_size=100352, layer_types=None,
                      d_model=2048, d_ff=8192, mamba_heads=64,
                      mamba_head_dim=64, n_groups=1, ssm_state=128,
                      conv_kernel=4, chunk=256, n_head=32, n_kv_head=8,
                      head_dim=64, tie_embeddings=True, **_):
    t, d = seq_len, d_model
    if layer_types is None:     # as published: attention at 5, 15, 25, 35
        layer_types = ["attention" if i % 10 == 5 else "mamba"
                       for i in range(40)]
    m_layers, full = layer_counts(layer_types)
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
        "mlp": 3 * d * d_ff,
    }
    mamba = per_token["mamba_projections"] \
        + per_token["mamba_convolution"] + per_token["mamba_scan"]
    attention = per_token["attention_projections"] + per_token["attention"]
    head = d * vocab_size
    layers = m_layers + full
    total = m_layers * mamba + full * attention + layers * per_token["mlp"] \
        + head
    fwd = 2 * total * t
    ssd = ssd_counts(seq_len, layer_types, mamba_heads, mamba_head_dim,
                     n_groups, ssm_state, chunk)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layers": {"state_space": m_layers, "full_attention": full},
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "mamba_mixers_share": m_layers * mamba / total,
            "mamba_scans_share": m_layers * per_token["mamba_scan"] / total,
            "attention_mixers_share": full * attention / total,
            "mlp_share": layers * per_token["mlp"] / total,
            "head_share": head / total,
            "parameters": parameters(vocab_size, layer_types, d, d_ff,
                                     mamba_heads, mamba_head_dim, n_groups,
                                     ssm_state, conv_kernel, n_head,
                                     n_kv_head, head_dim, tie_embeddings),
            "ssd_flops": ssd["flops"], "ssd_bytes": ssd["bytes"],
            "ssd_bytes_per_token_forward": ssd["bytes_per_token_forward"]}
