"""Pallas TPU kernels, each beside a plain-jnp oracle.

A kernel lives in a module of its own and is called through the jitted
wrappers of ``ops.py``. ``ref.py`` holds the oracle of every one, the ground
truth of tests/test_kernels.py (interpret mode); tests/test_tpu_compile.py
compiles each for a described TPU v5e at model widths.

On the model's path: ``ssm_state``, the Mamba-2 decode state's one pass
(``models/ssm.py`` ``ssm_decode_step``). Off it, called only by tests:
``flash_attention``, ``ssd_scan`` and ``blockcyclic``.
"""
