"""Inference stack: KV-cache decode, bucketed AOT programs, sampling,
continuous batching, speculative decoding.

Role map to the reference (SURVEY.md §2.7):
  model.py        ← examples/inference/modules/model_base.py (NeuronBaseModel)
  engine.py       ← trace/model_builder.py + model_wrapper.py + autobucketing.py
                    + NeuronBaseForCausalLM routing/_sample
  placement.py    ← (none: the physical layout fused gate_up weights and head-split
                    attention projections rest in on the device)
  sampling.py     ← src/neuronx_distributed/utils/sampling.py
  speculative.py  ← src/neuronx_distributed/utils/speculative_decoding.py
  benchmark.py    ← examples/inference/modules/benchmark.py
  runner.py       ← examples/inference/runner.py
"""

from neuronx_distributed_llama3_2_tpu.inference.benchmark import (
    GenerationBenchmark,
    LatencyCollector,
)
from neuronx_distributed_llama3_2_tpu.inference.engine import (
    ContinuousBatchingEngine,
    GenerateResult,
    GenerationConfig,
    InferenceEngine,
    default_buckets,
    pick_bucket,
)
from neuronx_distributed_llama3_2_tpu.inference.model import (
    CacheKind,
    HybridCache,
    JambaDecode,
    KVCache,
    LagunaDecode,
    LatentCache,
    LlamaDecode,
    MixedKVCache,
    MixtralDecode,
    PagedKVCache,
    RetentionDecode,
    SalaDecode,
    SmallThinkerDecode,
    SarvamDecode,
    XingDecode,
    StateCache,
    decode_model_for,
)
from neuronx_distributed_llama3_2_tpu.inference.sampling import (
    SamplingConfig,
    sample,
)
from neuronx_distributed_llama3_2_tpu.inference.runner import (
    benchmark_generation,
    check_accuracy_logits,
)
from neuronx_distributed_llama3_2_tpu.inference.speculative import (
    SpeculativeDecoder,
    SpeculativeResult,
)
from neuronx_distributed_llama3_2_tpu.inference.medusa import (
    MedusaBuffers,
    MedusaDecoder,
    MedusaHeads,
    MedusaResult,
    generate_medusa_buffers,
)
from neuronx_distributed_llama3_2_tpu.inference.mllama_decode import (
    MllamaCache,
    MllamaDecoder,
)

__all__ = [
    "ContinuousBatchingEngine",
    "GenerateResult",
    "GenerationBenchmark",
    "GenerationConfig",
    "InferenceEngine",
    "KVCache",
    "LatentCache",
    "LatencyCollector",
    "LlamaDecode",
    "MedusaBuffers",
    "MedusaDecoder",
    "MedusaHeads",
    "MedusaResult",
    "MixtralDecode",
    "MllamaCache",
    "MllamaDecoder",
    "PagedKVCache",
    "CacheKind",
    "HybridCache",
    "JambaDecode",
    "SalaDecode",
    "LagunaDecode",
    "SmallThinkerDecode",
    "MixedKVCache",
    "RetentionDecode",
    "SarvamDecode",
    "XingDecode",
    "StateCache",
    "SamplingConfig",
    "decode_model_for",
    "SpeculativeDecoder",
    "SpeculativeResult",
    "benchmark_generation",
    "check_accuracy_logits",
    "default_buckets",
    "generate_medusa_buffers",
    "pick_bucket",
    "sample",
]
