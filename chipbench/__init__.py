"""The chip benchmark: one cell (model configuration x traffic mix) per
run, driven by the files under ``configs/``, ``traffic/`` and
``metrics/``.  Entry point: ``python chipbench/run.py``."""
