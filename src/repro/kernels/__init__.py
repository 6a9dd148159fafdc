# Pallas TPU kernels for the paper's compute hot-spots (C4: distance
# computation), each with an ops.py jit wrapper and a ref.py pure-jnp
# oracle validated in interpret mode:
#   distance.py         pairwise (MXU) + rowwise (VPU) squared-L2, f32,
#                       and their −⟨x, y⟩ twins for the ip metric
#   int8.py             quantized-domain twins over QuantStore codes
#                       (int8×int8 MXU dots / int32 difference form)
#   bits.py             1-bit sketch Hamming distances over SketchStore
#                       codes (uint32 XOR + SWAR popcount, VPU)
#   nlj.py              fused exact join count (distance+compare+count)
#   gather_distance.py  scalar-prefetch fused neighbor-gather + distance
#   pdx.py              dimension-partitioned early-exit distances
