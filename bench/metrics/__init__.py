"""One reader per metric: ``<name>.py`` defines ``read(rec)``, which
returns the metric's value from the run's record, or None when the
record holds nothing for it (the metric is then left out of the line).

The record (``bench/run.py``): ``steps`` (one dict per step of the timed
window: ``dt`` seconds, ``tokens``, ``flops``, ``attn_flops``,
``attn_bytes``, ``padded``, ``cells``, ``waves``), ``window_s``,
``setup_s``, ``plan_wait_s``, ``compiles``, ``chips``, ``peaks``, and
with ``--trace 1`` ``trace`` (``bench/trace.reduce`` of the traced
window, plus that window's ``steps``)."""
