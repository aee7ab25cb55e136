"""Model FLOPs of one training example of the OLMoE decoder (Muennighoff et
al. 2024), from the configuration's shapes alone, and the operations and
bytes of its grouped expert matmuls for their roofline share.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, with ACTIVE
parameters only (a token passes through `top_k` of the `n_expert` experts;
the resident 64 are not what it computes), and the backward pass as twice
the forward. The causal attention needs half of the score and context
products, so half is counted, whatever the kernel computes. Not counted:
the embedding look-up, softmax, norms, rotary, the router's top-k, sort and
gathers, the optimizer, and anything the program computes twice.

Multiply-adds per token and layer at the published widths (d 2048, 16 heads,
64 experts top-8 of width 1024, vocabulary 50304, 4096 tokens): attention
projections 4 d^2 = 16.8 M, attention (causal half) T d = 8.4 M, experts
top_k * 3 d f = 50.3 M, router d E = 0.13 M; the head, once, d V = 103.0 M.
"""


def grouped_matmul_counts(seq_len, n_layer, d_model, n_expert, top_k,
                          d_expert, bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the grouped expert matmuls of
    one example: three projections (gate, up, down), each computed three
    times (forward, input gradient, weight gradient). Every one of the nine
    is M x d x f multiply-adds with M = seq_len * top_k rows (dropless: all
    assignments), and reads or writes one M x d activation, one M x f
    activation and one stack of expert matrices E x d x f, each once, in
    bf16 under AMP."""
    rows = seq_len * top_k
    products = 9 * n_layer
    flops = products * 2 * rows * d_model * d_expert
    values = rows * d_model + rows * d_expert + n_expert * d_model * d_expert
    return {"flops": flops, "bytes": products * values * bytes_per_value}


def flops_per_example(seq_len, vocab_size=50304, n_layer=16, d_model=2048,
                      n_head=16, n_expert=64, top_k=8, d_expert=1024, **_):
    t, d = seq_len, d_model
    per_token_layer = {
        "attention_projections": 4 * d * d,
        "attention": t * d,                 # QK^T and PV, causal half
        "experts": top_k * 3 * d * d_expert,
        "router": d * n_expert,
    }
    layer = sum(per_token_layer.values())
    head = d * vocab_size
    per_token = n_layer * layer + head
    fwd = 2 * per_token * t
    grouped = grouped_matmul_counts(seq_len, n_layer, d_model, n_expert,
                                    top_k, d_expert)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "multiply_adds_per_token_layer": per_token_layer,
            "multiply_adds_per_token_head": head,
            "decoder_layers_share": n_layer * layer / per_token,
            "experts_share": n_layer * per_token_layer["experts"] / per_token,
            "attention_share": n_layer * per_token_layer["attention"]
            / per_token,
            "head_share": head / per_token,
            "expert_matmul_flops": grouped["flops"],
            "expert_matmul_bytes": grouped["bytes"]}
