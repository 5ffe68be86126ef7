from distributed_sddmm_tpu.models.als import DistributedALS
from distributed_sddmm_tpu.models.gat import GAT, GATLayer
from distributed_sddmm_tpu.models.swa import SlidingWindowAttention

__all__ = ["DistributedALS", "GAT", "GATLayer", "SlidingWindowAttention"]
