"""Model architectures the benchmark runs, one module each:
``bench/arch/<name>.py``, named by a configuration file's ``"architecture"``
key.  A module gives everything that depends on the architecture, so a new
architecture is one new module and its configuration file:

- ``model_config(cfg)``: the engine's ``ModelConfig`` for a configuration
  file;
- ``dims(mc)``: the shapes and scalars the functions below take (hashable,
  a static argument of jitted functions);
- ``draw(m, key, embed_std, dtype)``: every weight from a JAX key, in the
  dtype served; traceable, ``bench/weights.py`` jits and places it;
- ``SPLIT_AXIS``: for each weight, the axis that is split over a cell's
  chips where the cell spans several;
- ``program_tree(w)``: the engine's parameter tree over those arrays;
- ``logit_gaps(m, w, seqs, length, quant=None, place=None)``: the plain
  float32 reference's check of served tokens, and with ``quant`` its
  lower-precision control, run layer by layer on ``place``;
- ``decode_work(m, positions, steps)`` and ``prefill_work(m, new_tokens,
  attn_lengths, dispatches)``: (flops, bytes) the algorithm requires,
  which ``bench/readers.py`` turns into roofline and MFU shares.
"""
