from repro.parallel.collectives import (
    hierarchical_grad_reduce, inter_pod_bytes_per_step,
    make_hierarchical_allreduce,
)
from repro.parallel.compression import (
    compress_with_feedback, compressed_psum, dequantize_int8, quantize_int8,
)
from repro.parallel.sharding import ShardingRules, named

__all__ = [
    "hierarchical_grad_reduce", "inter_pod_bytes_per_step",
    "make_hierarchical_allreduce", "compress_with_feedback", "compressed_psum",
    "dequantize_int8", "quantize_int8", "ShardingRules", "named",
]
