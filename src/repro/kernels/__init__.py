"""Pallas TPU kernels, each beside a plain-jnp oracle.

A kernel lives in a module of its own and is called through the jitted
wrappers of ``ops.py``. ``ref.py`` holds the oracle of every one, the ground
truth of tests/test_kernels.py (interpret mode); tests/test_tpu_compile.py
compiles each for a described TPU v5e at model widths.

On the model's path: ``ssm_state``, the Mamba-2 decode state's one pass,
in place in the stack of every Mamba layer's state at a traced layer
(``models/ssm.py`` ``ssm_decode_step``); ``kv_row``, a decode step's new
K and V rows written in place into the stacked cache where the chip keeps
the positions in the lanes (``models/attention.py``
``decode_attn_apply``). Off it, called only by tests:
``flash_attention``, ``ssd_scan`` and ``blockcyclic``.
"""
