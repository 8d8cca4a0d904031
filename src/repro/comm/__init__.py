"""Compressed client->server transport subsystem.

  codecs.py          uplink wire formats (int8/int4 blockwise quant,
                     1-bit sign-SGD + majority vote, top-k / random-k)
                     + measured byte sizes
  error_feedback.py  per-client EF residual state (carried through the
                     ScanDriver donated carry)

The int8 format is aggregated without decoding by the ``Int8`` block
loader of the Eq.-11 pipeline (kernels/robust_pipeline.py), which
``core/aggregation.aggregate(..., enc=)`` selects.
"""
from repro.comm import codecs, error_feedback  # noqa: F401
