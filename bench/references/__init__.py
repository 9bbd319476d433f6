"""One module per family of model, found by the name a configuration file
gives under ``reference``: ``references/<reference>.py``.  It is the one
place that knows the family's mathematics, and imports nothing of the
program under test.  It exports:

- ``shapes(m)``: the weights as the program lays them out, name ->
  (shape, std), std 0 meaning ones (``bench/weights.py`` makes them);
- ``logits(params, m, tokens, start, int8=False)``: the plain float32
  reference, and with ``int8`` the control that ``correct`` is set
  against;
- ``prefill_flops(m, prompt_len)``, ``decode_flops(m, keys)`` and
  ``decode_step_bytes(m, keys)``: the operations and bytes the roofline
  readers under ``metrics/`` divide by, from the shapes alone, on the
  shared helpers of ``bench/flops.py``.

``m`` is the configuration file's ``model`` block, spec blocks (``moe``,
``ssm``, ``encoder``) included as nested objects.  A family may export
more counts for readers of its own, which are new files.
"""
