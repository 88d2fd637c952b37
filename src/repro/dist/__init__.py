"""First-class distribution layer.

* ``sharding``    — logical-axis -> mesh-axis policies: ``param_shardings``,
                    ``cache_shardings``, ``input_shardings``, ``batch_pspec``.
* ``annotate``    — activation-sharding constraints (``constrain_batch``,
                    ``constrain_vocab``) driven by launcher-set batch axes.
* ``collectives`` — wire-compressed collectives: ``compressed_pmean`` (the
                    ``grad_compress`` knob) and ``pod_sync_params`` (the
                    ``sync_period`` knob's periodic pod-level sync).
"""
