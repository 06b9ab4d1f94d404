"""Test shim: breaks the timed path underneath the harness.

On ``PYTHONPATH`` of the server child only in ``test_correct.py``. With
``BENCH_FAULT=alter_answer`` every third answer has its last track replaced
where the engine composes it; the harness must then report not correct.
``BASE_DIR`` is set for the server child alone, so the harness process and
its other children are left as they are.
"""

import os

if os.environ.get("BENCH_FAULT") == "alter_answer" and os.environ.get("BASE_DIR"):
    from kmlserver_tpu.serving import engine as _engine

    _compose = _engine.RecommendEngine._compose_answer
    _calls = [0]

    def _altered(self, bundle, seeds, *rest):
        songs, source = _compose(self, bundle, seeds, *rest)
        _calls[0] += 1
        if songs and _calls[0] % 3 == 0:
            wrong = next(n for n in bundle.vocab if n not in songs and n not in seeds)
            songs = list(songs[:-1]) + [wrong]
        return songs, source

    _engine.RecommendEngine._compose_answer = _altered
