"""Model zoo: the reference's five parity workloads, in flax.linen.

Mirrors SURVEY.md §2 workload rows / BASELINE.json "configs":

- LeNet-5 (MNIST, single-chip sanity — SURVEY.md §3e)
- ResNet-20 (CIFAR-10, sync DP) and ResNet-50 (ImageNet, the north-star)
- Inception-v3 (ImageNet, async-stale flavor)
- BERT-base (pretraining, MLM+NSP; large embedding allreduce)

All models are pure graph-builders like the reference's ``inference()``/
``loss()`` functions (SURVEY.md §1 L5) — but as flax modules whose params are
an explicit pytree, so placement is a sharding annotation instead of a
``replica_device_setter`` device scope.
"""

from distributed_tensorflow_tpu.models.lenet import LeNet5  # noqa: F401
from distributed_tensorflow_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet20,
    ResNet50,
)
from distributed_tensorflow_tpu.models.inception import InceptionV3  # noqa: F401
from distributed_tensorflow_tpu.models.bert import (  # noqa: F401
    BertConfig,
    BertForPreTraining,
    BertModel,
    bert_base,
    make_bert_pretraining_loss,
)
from distributed_tensorflow_tpu.models.causal_lm import (  # noqa: F401
    CausalLM,
    CausalLMConfig,
    causal_lm_base,
    causal_param_specs,
    make_causal_lm_loss,
    sample_tokens,
)
from distributed_tensorflow_tpu.models.sambay import (  # noqa: F401
    SambaY,
    SambaYConfig,
    sambay_init_params,
)
from distributed_tensorflow_tpu.models.olmo_hybrid import (  # noqa: F401
    OlmoHybrid,
    OlmoHybridConfig,
    olmo_hybrid_init_params,
)
from distributed_tensorflow_tpu.models.deepseek_v2 import (  # noqa: F401
    DeepseekV2,
    DeepseekV2Config,
    deepseek_v2_init_params,
)
