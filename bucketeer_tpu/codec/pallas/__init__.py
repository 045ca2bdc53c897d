"""Hand-written Pallas TPU kernels.

Three kernels carry Tier-1 on device:

- :mod:`.cxd_scan` — the stripe-parallel EBCOT CX/D scan (context
  modeling), keeping a code-block's significance state and symbol
  buffer resident in VMEM for the whole Mb-clamped plane walk instead
  of letting XLA spill the batched scan state through HBM (the tensor
  codec's ``replay`` backend).
- :mod:`.fused_t1` — the device-MQ path: the CX/D scan
  chained straight into the MQ arithmetic coder inside one kernel, the
  symbol buffer a kernel-local VMEM value that never touches HBM, so
  finished per-pass byte segments (not symbol streams, not work) are
  all that ever reaches the host (the tensor codec's ``device``
  backend).
- :mod:`.mq_scan` — the standalone MQ coder kernel, the per-block
  parity/oracle surface for the fused kernel's back half.

Status: interpret-only. The chip's compiler (Mosaic) refuses all three
(``cxd.MOSAIC_REFUSAL``: block shapes not aligned to (8, 128), then
``dynamic_slice`` in the shared ``_cxd_single`` body), so the fused jnp
programs are the device Tier-1 on every backend, and
``BUCKETEER_CXD_PALLAS=1`` raises at program build. Every kernel shares
its step function with the jnp path, and interpret-mode parity tests
(tests/test_cxd.py, tests/test_mq_device.py) pin them to each other and
to the codec/t1.py + codec/mq.py reference coders.

When adding kernels, read the TPU guide under /opt/skills/guides/ first
and keep a jnp fallback for the CPU backend.
"""
