"""Utilities: recorder, checkpointing, helper functions, the cross-replica
divergence check and the convergence gates (``converge``, ``rulecomp``).

Reference (unverified — SURVEY.md §2.1): ``theanompi/lib/recorder.py`` and
``theanompi/lib/helper_funcs.py``.
"""

from theanompi_tpu.utils.recorder import Recorder

__all__ = ["Recorder"]
