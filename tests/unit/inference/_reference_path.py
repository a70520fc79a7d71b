"""An int8 engine held to the per-projection programs: the fused decode
block's reference. The engine picks the path from what it observes
(``InferenceEngine._fused_decode_eligible``) and offers no option, so a test
that needs both paths of one configuration steers the verdict itself."""

from deepspeed_tpu.inference.engine import FusedDecodeEligibility, InferenceEngine

REASON = "test: per-projection reference"


def per_projection_engine(monkeypatch, build, *args, **kw):
    """``build(*args, **kw)`` with the fused path's gate refusing, at
    construction and for as long as the engine lives."""
    verdict = FusedDecodeEligibility([REASON])
    with monkeypatch.context() as m:
        m.setattr(InferenceEngine, "_fused_decode_eligible", lambda self: verdict)
        eng = build(*args, **kw)
    monkeypatch.setattr(eng, "_fused_decode_eligible", lambda: verdict)
    return eng
