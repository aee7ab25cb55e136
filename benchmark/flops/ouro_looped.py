"""Model FLOPs of one training example of the Ouro looped decoder (Zhu et al.
2025, arXiv:2510.25741), from the configuration's shapes alone, and the
operations and bytes of its attention blocks for the flash kernels' roofline
share.

One example is one sequence of `seq_len` tokens. The stack of `n_layer`
layers is applied `n_loop` times and the head `n_loop` times, so a step holds
`n_layer * n_loop` layer applications and `n_loop` heads. Counted: every
matrix multiplication of the forward pass at 2 FLOPs a multiply-add, and the
backward pass as twice the forward. The causal attention needs half of the
score and context products, so half is counted, whatever the kernel computes.
Not counted: the embedding look-up, softmax, the four norms a layer, rotary,
the gate's sigmoid and the exit distribution, the optimizer, and anything the
program computes twice.

Multiply-adds per token and layer application at the published widths (d
2048, 16 heads of 128, feed-forward 5632, vocabulary 49152, 4096 tokens):
attention projections 4 d^2 = 16.8 M, attention (causal half) T d = 8.4 M,
gated feed-forward 3 d f = 34.6 M: 59.8 M; a head d V = 100.7 M; a gate d.
"""


def attention_counts(seq_len, n_layer, d_model, n_loop, bytes_per_value=2):
    """FLOPs and HBM bytes a step needs for the attention blocks of one
    example: `n_layer * n_loop` blocks, each seven `T x T x d_model`
    products over all heads together (forward: scores, context; backward:
    scores again, dP, dV, dK, dQ), of which a causal mask needs half; each
    reads or writes q, k, v, Out, dOut, dq, dk, dv `[T, d_model]` once, in
    bf16 under AMP (the rows' float32 log-sum-exp is 1/64 of one of those
    and is left out)."""
    blocks = n_layer * n_loop
    flops = blocks * 7 * 2 * seq_len * seq_len * d_model // 2
    values = 8 * seq_len * d_model
    return {"flops": flops, "bytes": blocks * values * bytes_per_value}


def flops_per_example(seq_len, vocab_size=49152, n_layer=48, d_model=2048,
                      d_ff=5632, n_loop=4, **_):
    t, d = seq_len, d_model
    per_token_application = {
        "attention_projections": 4 * d * d,
        "attention": t * d,                 # QK^T and PV, causal half
        "feed_forward": 3 * d * d_ff,
    }
    application = sum(per_token_application.values())
    head = d * vocab_size
    gate = d
    looped = n_layer * n_loop * application
    heads = n_loop * head
    per_token = looped + heads + (n_loop - 1) * gate
    fwd = 2 * per_token * t
    attention = attention_counts(seq_len, n_layer, d_model, n_loop)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t,
            "layer_applications": n_layer * n_loop,
            "multiply_adds_per_token_application": per_token_application,
            "multiply_adds_per_token_head": head,
            "looped_stack_share": looped / per_token,
            "heads_share": heads / per_token,
            "attention_share": n_layer * n_loop
            * per_token_application["attention"] / per_token,
            "attention_flops": attention["flops"],
            "attention_bytes": attention["bytes"]}
