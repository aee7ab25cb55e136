"""Model FLOPs of one training example of the Phi-4-mini-flash decoder-decoder
hybrid (a held run of the model's own layers: Mamba-1 mixers, differential
attention under a window and full, gated memory units, cross attention on
another layer's keys and values, a gated MLP in every layer, one table as
embedding and head), from the configuration's shapes alone, and the
operations and bytes of the selective scan and of the differential attention
for their roofline shares.

One example is one sequence of `seq_len` tokens. Counted: every matrix
multiplication of the forward pass at 2 FLOPs a multiply-add, the
convolution's taps, the scan's three multiply-adds a (channel, state) and
token, and the backward pass as twice the forward. A causal layer needs half
of its score and value products, so half is counted, whatever the kernel
computes; a window layer the window's keys a query (fewer for the first
`window` queries: counted exactly). A differential layer has TWO softmax
maps a pair of heads, each with a query and key head of `head_dim` and the
pair's values of `2 head_dim`: `n_head (head_dim + 2 head_dim)`
multiply-adds a (query, key). The tied table is counted once, as the head's
product (the look-up is no product). Not counted: softmax, norms, softplus
and the scan's exponentials (one a (channel, state) and token: 335.5 M a
layer a pass at the cell's sizes, on the transcendental unit, for which
`peaks.json` holds no peak), silu, lam, the optimizer, and anything the
program computes twice.

Multiply-adds per token at the published widths (d 2560; 4096 tokens; the
model's layers 14-19). A Mamba mixer's four projections: W_in 2560 x 10240 =
26.21 M, W_x 5120 x 192 = 0.98 M, W_dt 160 x 5120 = 0.82 M, W_out 5120 x 2560
= 13.11 M: 41.12 M (two layers 82.2 M), its convolution 0.02 M and scan 0.25
M. A differential layer's projections: q, k, v 2560 x 5120 and o 2560 x 2560 =
19.66 M; the cross layer's q and o 13.11 M: 52.4 M in three layers. Scores and
values 40 x 192 = 7680 a (query, key): a full-causal layer T / 2 keys = 15.73
M (two layers, 17 and 19: 31.5 M), the window layer ~512 keys = 3.7 M. The
gated memory unit 2 x 2560 x 5120 = 26.2 M. Every layer's MLP 3 x 2560 x 10240
= 78.64 M (six: 471.9 M). The head, once, 2560 x 25008 = 64.0 M. 732.5 M in
all = 1465 MFLOP a token forward, 18.0 TFLOP a step of 4096 tokens forward
and backward: the MLPs 64%, the Mamba mixers 11%, attention's projections
7%, its scores and values 5%, the gated memory unit 4%, the head 9%.
"""

KINDS = ("mamba", "window", "full", "gmu", "cross")


def layer_kind(l, n_layer=32, mb_per_layer=2):
    """The published rule (`paddle_tpu/models/phi4_flash.py::layer_kind`,
    copied: the benchmark stands alone)."""
    half = n_layer // 2
    if l % mb_per_layer == 0:
        return "mamba" if l < half + 2 else "gmu"
    if l < half:
        return "window"
    return "full" if l == half + 1 else "cross"


def layer_counts(n_layer, mb_per_layer, first_layer, layers_held):
    """{kind: layers} of the held run."""
    held = range(first_layer, n_layer if layers_held is None
                 else first_layer + layers_held)
    kinds = [layer_kind(l, n_layer, mb_per_layer) for l in held]
    return {k: kinds.count(k) for k in KINDS}


def window_keys(seq_len, window):
    """The mean keys a query sees under a causal window."""
    w = min(window, seq_len)
    return (w * (w + 1) // 2 + (seq_len - w) * w) / seq_len


def selective_scan_cost(seq_len, layers, inner, state, chunk=128):
    """`selective_scan_flops` and `selective_scan_bytes`: FLOPs and HBM bytes
    a step needs for the scans of one example, all Mamba layers, forward and
    backward (twice the forward): the work the OPERATOR needs, whatever
    implements it. FLOPs: three multiply-adds a (channel, state) and token
    (the state's decay, what is written to it, what is read of it). Bytes a
    token and layer forward: x and dt_raw `[I]` as they arrive (bf16 under
    AMP), y `[I]` float32, B and C `[N]` bf16, and the state a chunk of 128
    tokens hands on, `[I, N]` float32 once a chunk."""
    token = inner * (2 + 2 + 4) + 2 * state * 2 + inner * state * 4 // chunk
    return {"flops": layers * 3 * 2 * 3 * inner * state * seq_len,
            "bytes": layers * 3 * seq_len * token,
            "bytes_per_token_forward": token}


def diff_attention_cost(seq_len, counts, window, n_head, n_kv_head, head_dim):
    """`diff_attention_flops` / `diff_attention_bytes` (all three attention
    layers' scores and values) and `diff_window_flops` / `diff_window_bytes`
    (the window layers' alone), forward and backward, a step of one example.
    Bytes a token and layer forward, bf16: q `n_head head_dim`, both maps'
    results `n_head 2 head_dim`, and where the layer makes them its k and v
    `2 n_kv_head head_dim` (a cross layer reads layer 17's: counted for it as
    well, they are read again)."""
    pair = n_head * 3 * head_dim            # multiply-adds a (query, key)
    causal = counts["full"] + counts["cross"]
    full = causal * pair * seq_len / 2
    win = counts["window"] * pair * window_keys(seq_len, window)
    token = 2 * (n_head * head_dim + 2 * n_head * head_dim
                 + 2 * n_kv_head * head_dim)
    return {"diff_attention_flops": 3 * 2 * seq_len * (full + win),
            "diff_attention_bytes": 3 * seq_len * token
            * (causal + counts["window"]),
            "diff_window_flops": 3 * 2 * seq_len * win,
            "diff_window_bytes": 3 * seq_len * token * counts["window"]}


def parameters(vocab_size, counts, d_model, d_ff, n_head, n_kv_head, head_dim,
               ssm_state, conv_kernel, expand, dt_rank):
    """The parameters this chip holds: what the configuration's `deployment`
    states."""
    d, inner = d_model, expand * d_model
    mamba = d * 2 * inner + inner * (conv_kernel + 1) \
        + inner * (dt_rank + 2 * ssm_state) + dt_rank * inner + inner \
        + inner * ssm_state + inner + inner * d
    differential = 4 * head_dim + 2 * head_dim
    out = n_head * head_dim * d + d
    query = d * n_head * head_dim + n_head * head_dim
    attention = query + 2 * (d * n_kv_head * head_dim + n_kv_head * head_dim) \
        + out + differential
    cross = query + out + differential
    layers = sum(counts.values())
    return counts["mamba"] * mamba \
        + (counts["window"] + counts["full"]) * attention \
        + counts["cross"] * cross + counts["gmu"] * 2 * d * inner \
        + layers * (3 * d * d_ff + 4 * d) + 2 * d + vocab_size * d


def flops_per_example(seq_len, vocab_size=200064, n_layer=32, mb_per_layer=2,
                      window=512, first_layer=0, layers_held=None,
                      d_model=2560, d_ff=10240, n_head=40, n_kv_head=20,
                      head_dim=64, ssm_state=16, conv_kernel=4, expand=2,
                      dt_rank=None, **_):
    t, d = seq_len, d_model
    inner = expand * d
    dt_rank = dt_rank or -(-d // 16)
    counts = layer_counts(n_layer, mb_per_layer, first_layer, layers_held)
    pair = n_head * 3 * head_dim
    per_token = {
        "mamba_projections": d * 2 * inner + inner * (dt_rank + 2 * ssm_state)
        + dt_rank * inner + inner * d,
        "mamba_convolution": inner * conv_kernel,
        "mamba_scan": 3 * inner * ssm_state,
        "attention_projections": 2 * d * n_head * head_dim
        + 2 * d * n_kv_head * head_dim,
        "cross_projections": 2 * d * n_head * head_dim,
        "attention_full": pair * t / 2,         # both maps, causal half
        "attention_window": pair * window_keys(t, window),
        "gated_memory": 2 * d * inner,
        "mlp": 3 * d * d_ff,
    }
    mamba = counts["mamba"] * (per_token["mamba_projections"]
                               + per_token["mamba_convolution"]
                               + per_token["mamba_scan"])
    projections = (counts["window"] + counts["full"]) \
        * per_token["attention_projections"] \
        + counts["cross"] * per_token["cross_projections"]
    scores = (counts["full"] + counts["cross"]) * per_token["attention_full"] \
        + counts["window"] * per_token["attention_window"]
    gmu = counts["gmu"] * per_token["gated_memory"]
    layers = sum(counts.values())
    head = d * vocab_size
    total = mamba + projections + scores + gmu + layers * per_token["mlp"] \
        + head
    fwd = 2 * total * t
    scan = selective_scan_cost(t, counts["mamba"], inner, ssm_state)
    return {"forward": fwd, "forward_backward": 3 * fwd,
            "positions_per_example": t, "layers": counts,
            "multiply_adds_per_token": per_token,
            "multiply_adds_per_token_head": head,
            "mamba_mixers_share": mamba / total,
            "attention_projections_share": projections / total,
            "attention_scores_share": scores / total,
            "gated_memory_share": gmu / total,
            "mlp_share": layers * per_token["mlp"] / total,
            "head_share": head / total,
            "parameters": parameters(vocab_size, counts, d, d_ff, n_head,
                                     n_kv_head, head_dim, ssm_state,
                                     conv_kernel, expand, dt_rank),
            "selective_scan_flops": scan["flops"],
            "selective_scan_bytes": scan["bytes"],
            "selective_scan_bytes_per_token_forward":
                scan["bytes_per_token_forward"],
            **diff_attention_cost(t, counts, window, n_head, n_kv_head,
                                  head_dim)}
