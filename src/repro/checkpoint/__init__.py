from repro.checkpoint.partition import (  # noqa: F401
    SHARD_FORMAT, StaleCheckpointError, ensure_partitioned,
    ensure_quantized, is_current, load_manifest, load_shard,
    partition_and_save, requantize, shard_names)
from repro.checkpoint.quant import (  # noqa: F401
    QUANT_SCHEMES, QuantizedTensor, dequant_tree, quantize_array)
