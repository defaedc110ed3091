"""Pallas TPU kernels of the serving path: ``fused_decode`` (decode KV
write + attention, bf16 and int8 pools), ``prefill_attention`` and
``kv_write``. ``ops/attention.py`` decides where each one serves and
imports it there; nothing is re-exported here."""
