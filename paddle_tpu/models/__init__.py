"""Model zoo mirroring the reference benchmark configs
(reference: benchmark/fluid/models/{mnist,resnet,vgg,stacked_dynamic_lstm,
machine_translation}.py) plus Transformer-base and DeepFM (the BASELINE.json
target workloads), OLMoE: a sparse-expert decoder LM at a published width
(the first model whose loss is a cross-entropy plus two router losses), and
Ouro: a looped decoder LM (the first model that uses a weight more than once
a step: one stack of layers applied four times, four heads, an exit gate),
and Qwen3-Next: gated-delta-rule linear-attention layers beside gated softmax
attention, over one chip's share of a renormalised top-k expert layer with a
shared expert (the first model built for a share of a stated deployment),
and Kanana-2: latent attention (MLA, query/key heads of 192 over value heads
of 128 through the flash kernels), a leading dense layer, and a sigmoid
router with a selection bias that the step itself rewrites (the first LM
with non-trainable state beside its weights), and Mellum2: sliding-window
and full attention layers mixed 3:1 through the flash kernels, each kind
with rotary tables of its own (YaRN on the full layers), 32 query heads
over 4 key-value heads (the first model whose attention layers differ in
their mask), and Trinity-Mini: window layers that turn by rotary beside full
layers that carry no positions at all, an output gate from a projection of
its own, a norm on both sides of every sublayer with experts inside, a scaled
embedding (the first model whose attention kinds differ in whether they
turn), and Keye-VL-2.0's language model: an indexer that picks the keys each
query attends to, the flash kernels masked by that set (the first model whose
mask is data, and the first with parameters that the loss cannot reach),
and Nemotron-H: Mamba-2 state-space mixers (a chunked selective scan), softmax
attention without positions and two-matrix relu² experts under a sigmoid
router, one sublayer a layer by a pattern string (the first model whose
layers are a mixer or a feed-forward part alone), and Ling-3.0-flash-VL's
language model: Kimi Delta Attention (a delta rule whose decay is per key
channel) in five layers of six beside latent attention under a head-wise
gate, over a group-limited sigmoid router (the first model built as a run of
its published layers from a stated index on, and the first whose router
chooses its groups before its experts), and Olmo-Hybrid: a dense 3:1 hybrid
of the gated delta rule with beta in (0, 2) (a transition with a negative
eigenvalue, heads of 96 / 192) and OLMo's whole-projection QK-norm attention,
every sublayer normed on the way out only (the first model built for a share
of a layer's HEADS), and Granite 4.0-H: Mamba-2 mixers of ONE group of 64
heads at chunk 256 beside unrotated attention at a published softmax scale, a
gated feed-forward in every layer, scalar multipliers on the embedding, the
residual branches and the logits (the first model whose head is its
embedding's table, and the first whose every layer is a mixer AND a
feed-forward under a list of layer types with Mamba in it), and LFM2-MoE:
gated short convolutions (three causal taps a channel with NO activation,
between two gates that are projections of the same input) in three layers of
four beside QK-normed rotary attention, routed experts with no shared expert
under a sigmoid router whose renormalisation publishes its epsilon, a tied
table (the first model most of whose mixers hold neither attention nor a
recurrence), and Phi-4-mini-flash: Mamba-1 mixers (a selective scan whose decay
is per channel and per state) beside differential attention (two softmax maps
a pair of heads, their difference under a learned scalar) under a window and
full, gated memory units on an earlier layer's scan output and cross
attention on an earlier layer's keys and values, LayerNorm (the first model
whose layers read what other layers computed, and the first built as a run of
its own layers that a later stage would be handed memory from)."""

from . import mnist  # noqa: F401
from . import resnet  # noqa: F401
from . import vgg  # noqa: F401
from . import stacked_dynamic_lstm  # noqa: F401
from . import transformer  # noqa: F401
from . import deepfm  # noqa: F401
from . import machine_translation  # noqa: F401
from . import se_resnext  # noqa: F401
from . import tiny_lm  # noqa: F401
from . import olmoe  # noqa: F401
from . import ouro  # noqa: F401
from . import qwen3_next  # noqa: F401
from . import kanana2  # noqa: F401
from . import mellum2  # noqa: F401
from . import trinity  # noqa: F401
from . import keye_vl2  # noqa: F401
from . import nemotron_h  # noqa: F401
from . import ling3  # noqa: F401
from . import olmo_hybrid  # noqa: F401
from . import granite_hybrid  # noqa: F401
from . import lfm2_moe  # noqa: F401
from . import phi4_flash  # noqa: F401
