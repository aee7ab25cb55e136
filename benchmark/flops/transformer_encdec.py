"""Model FLOPs of one training example of the encoder-decoder Transformer
("Attention Is All You Need"), from the configuration's shapes alone.

One example is a sentence pair padded to `seq_len`: `seq_len` source and
`seq_len` target positions. Counted: every matrix multiplication of the
forward pass (2 FLOPs per multiply-add), and the backward pass as twice the
forward (one product for the input gradient, one for the weight gradient).
Not counted: embedding look-ups, softmax, LayerNorm, dropout, the optimizer,
and anything the program recomputes. The causal decoder self-attention needs
half of the score and context products, so half is counted, whatever the
kernel computes.
"""


def flops_per_example(seq_len, n_layer=6, n_head=8, d_model=512, d_inner=2048,
                      src_vocab_size=30000, trg_vocab_size=30000, **_):
    t, d = seq_len, d_model
    attn_proj = 4 * d * d               # q, k, v, o of one attention block
    ffn = 2 * d * d_inner
    enc_layer = attn_proj + ffn
    dec_layer = 2 * attn_proj + ffn     # self + cross attention
    weights_per_position = (n_layer * (enc_layer + dec_layer)
                            + d * trg_vocab_size)      # + output projection
    fwd_weights = 2 * weights_per_position * t
    # scores QK^T and context PV: 2 * t * t * d multiply-adds a block,
    # all heads together
    one_block = 2 * 2 * t * t * d
    blocks = n_layer * (1 + 0.5 + 1)    # enc self, causal dec self, cross
    fwd_attention = blocks * one_block
    fwd = fwd_weights + fwd_attention
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "attention_share": fwd_attention / fwd,
            "positions_per_example": t}
