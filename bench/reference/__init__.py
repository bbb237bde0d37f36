"""Plain references, one module per ``model_type`` of a configuration file.

Each module gives ``QK_NORM``, ``QKV_BIAS`` and ``program_fields(cfg)``: the
program's ``ModelConfig`` fields that the published ``config.json`` keys
set.  The forward itself is ``dense.forward_logits``.
"""
