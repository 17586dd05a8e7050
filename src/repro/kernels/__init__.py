# OPTIONAL layer: custom kernels for the compute hot-spots this repo
# optimizes, each with a pure-jnp oracle in ref.py and a jit'd public
# wrapper in ops.py (native lowering on TPU; interpret=True on request):
#   client_conv     — stacked per-client conv as im2col batched GEMM
#                     (einsum autodiff primal + Pallas panel GEMM)
#   masked_adam     — fused masked-Adam update (AdaSplit eq. 7)
#   flash_attention — blocked attention for the LM serving path
#   ntxent          — NT-Xent statistics (eq. 5)
#   soft_threshold  — L1 proximal operator
