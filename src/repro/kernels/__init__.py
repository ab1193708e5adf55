"""Pallas TPU kernels for the PS hot path (+ pure-jnp oracles in ref.py).

Layout of the package:

* ``embedding_bag``  — pooled lookup forward + **sorted-scatter** backward,
  both **DMA-streamed**: the (V, D) table and the sorted (E, D) gradient
  rows live in HBM (``pl.ANY``) and move through double-buffered VMEM
  scratch blocks with ``pltpu.make_async_copy``, so VMEM residency is
  O(block_v * block_d + chunk_e * block_d) at any vocabulary size.  The
  B*F (id, row) pairs are sorted by id once, per-vocab-block segment
  boundaries come from a searchsorted, and the backward grid runs one
  program per disjoint (BLOCK_V, BLOCK_D) output tile — parallel,
  race-free, with per-ID contributor counts produced in the same pass
  (Alg. 2 line 23).  ``embedding_bag_grad_resident`` keeps the sorted
  arrays whole in VMEM: a bit-exactness oracle for the DMA transport.
* ``gba_apply``      — the fused PS apply: token-decay aggregation over the
  flat (M, N_total) gradient buffer AND the Adagrad update in one VMEM
  pass; fed by ``repro.core.gba.FlatLayout`` (dense pytree leaves raveled
  back-to-back with an offsets table) so the whole apply is one launch.
* ``gba_aggregate``  — standalone decayed reduction (M, D) -> (D,); kept
  for tree-level use, superseded on the train path by ``gba_apply``.
* ``fused_adagrad``  — standalone one-pass Adagrad; same story.
* ``flash_decode``   — decode-time attention for the serving stack.
* ``ops``            — jit'd wrappers with per-call ``interpret=`` control.
* ``runtime``        — interpret-mode resolution (platform default, env
  var ``REPRO_INTERPRET``, ``set_interpret``).

Every kernel has an allclose oracle in ``ref`` and a parity sweep in
``tests/test_kernels.py`` (+ ``tests/test_embedding_stream.py`` for the
streamed paths), run in interpret mode on the CPU.
``tests/test_tpu_compile.py`` compiles the main-path kernels at real
widths for a TPU v5e, and ``chip_smoke.py`` runs ``gba_apply`` and the
streamed embedding kernels compiled on a v5e chip against their ``ref``
oracles (``gba_apply`` within 1.9e-9 on the params, the embedding
forward within 3e-8, the backward and counts exact).  ``quantize`` and
``fused_adagrad`` compile for the chip but no chip run has executed them.
"""
