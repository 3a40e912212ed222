"""Pallas TPU kernels for PM-LSH's compute hot spots.

kernels:
  pairwise_dist — candidate VERIFICATION: exact d-dim distances (MXU)
  project_dist  — fused ESTIMATE: x@A then ||·-q'||², projection stays in VMEM
  topk          — streaming answer top-k (selection network, k ≤ 128)
  select        — radius-threshold SELECT: Eq. 9-seeded r·c^i ladder +
                  bisection + per-tile matmul-rank packing; handles the
                  T = βn + k candidate budget without O(n·T) sort work
  verify        — gather-free VERIFY: DMAs candidate rows HBM→VMEM
                  tile-by-tile, exact distances + streaming top-k in
                  VMEM; the (B,T,d) candidate tensor never exists
  adc           — quantized RERANK: asymmetric distances over codes via
                  per-query LUTs (one-hot MXU contraction)
  pair_join     — closest-pair SELF-JOIN: band-major tiles over the
                  (n, n) pair space, streaming top-k pair heap (the ub
                  register) in VMEM, Alg. 4's radius filter as tile
                  masking over a 1-D projection sort
ops  — jit'd public wrappers (backend-aware dispatch)
ref  — pure-jnp oracles (the semantics contract; tests sweep against these)
"""
from . import ops, ref  # noqa: F401
