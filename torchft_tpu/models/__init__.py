"""Model families. Re-exported here: the GPT transformer (and the one
step maker every family trains through, ``make_train_step`` /
``make_grad_step``), Llama, the toy MoE transformer and the MLP. The
families the benchmark trains at published widths are modules of their
own, each with ``<Family>Config``, ``init_params``, ``forward_hidden``,
``loss_terms`` and ``loss_fn`` (the step maker's ``loss=``): ``olmoe``,
``joyai``, ``nemotron_h``, ``lfm2``, ``kimi_linear`` (which calls
``joyai``'s latent-attention and sparse sublayers with its own config),
``phi4flash`` (a stack that is not a chain: two layers hand their scan
output and their keys and values on to later layers), ``smallthinker``
(attention windowed or full and rotated or not by two lists of the
config, a router that reads the stream before attention, ReGLU experts),
``olmo_hybrid`` (a dense stack of scalar-gated delta-rule mixers 3 : 1
with unrotated full attention, the OLMo family's norms on each sublayer's
output) and ``laguna`` (attention whose query head count, mask and rotation
follow the kind of layer, a YaRN rotation over half a head beside a plain
one, a gate a head, a dense first layer and then sigmoid-routed experts
beside a shared one) and ``qwen3_next`` (a pre-norm stack with zero-centred
norm weights: a delta rule whose value heads share key heads 3 : 1 with
attention on 256-wide heads under an element-wise gate and a quarter-head
rotation, every layer a softmax top-10-of-512 router beside a gated shared
expert) and ``keye`` (attention whose keys are chosen by the data: an
indexer scores every causal pair, each query keeps its 2 048 best keys and
attends to those alone — ``ops/dsa.py`` —, the indexer's own KL term beside
the cross entropy, a rotation from three position streams, a softmax
top-8-of-128 router) and ``granite_hybrid`` (a dense stack whose layer is
a mixer THEN a SwiGLU under two norms with a multiplier on each branch:
Mamba-2 mixers whose 64 heads share one ``B`` and ``C`` 9 : 1 with
position-free attention at the config's own softmax scale, the
embedding's and the logits' scalings around one tied table) and ``ouro``
(a stack whose layers run ``total_ut_steps`` times on ONE set of weights —
a scan over the passes, a layer's gradient the sum over its visits —, four
norms a layer, a head and a cross entropy after every pass through one
weighted sweep of ``ops/xent.py``, an exit gate whose distribution over the
passes weighs the losses under an entropy term, its statistics carried to
the optimizer's gauges in the gradient tree); what more than one of them
computes is in ``common``."""

from torchft_tpu.models.mlp import (  # noqa: F401
    init_linear,
    init_mlp,
    linear_forward,
    mlp_forward,
)
from torchft_tpu.models.llama import (  # noqa: F401
    LLAMA_CONFIGS,
    LlamaConfig,
    llama_forward,
    llama_init_params,
    llama_loss_fn,
)
from torchft_tpu.models.moe_transformer import (  # noqa: F401
    MOE_CONFIGS,
    MoETransformerConfig,
    make_moe_train_step,
    moe_init_params,
    moe_transformer_loss_fn,
)
from torchft_tpu.models.transformer import (  # noqa: F401
    CONFIGS,
    TransformerConfig,
    count_params,
    forward,
    init_params,
    loss_fn,
    make_grad_step,
    make_train_step,
)
