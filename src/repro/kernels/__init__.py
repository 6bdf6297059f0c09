"""Pallas TPU kernels for the paper's compute hot-spots.

 - dorefa.py    : quantize / dequantize / fused q->dq (pl.pallas_call + BlockSpec)
 - aggregate.py : fused dequant + weighted server aggregation
 - sic_rates.py : batched NOMA SIC group scoring (scheduler candidate batches)
 - ops.py       : jit'd public wrappers (padding, scale pass, jnp fallback)
 - platform.py  : interpret (CPU) vs Mosaic (TPU), decided per call
 - ref.py       : pure-jnp oracles used by the allclose test sweeps
"""
