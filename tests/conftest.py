import functools
import os

import pytest

# Tests must see the single real CPU device (the 512-device override is
# strictly dryrun.py's business).
os.environ.pop("XLA_FLAGS", None)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

try:
    from hypothesis import HealthCheck, settings
except ModuleNotFoundError:
    # Minimal-environment shim: the property-based test modules import
    # ``given``/``settings``/``strategies`` at collection time. Install a
    # stub so the suite still collects and runs; every hypothesis-driven
    # case SKIPs instead of erroring the whole session.
    import sys
    import types

    class _Strategy:
        """Inert stand-in accepted anywhere a SearchStrategy is expected."""

        def __call__(self, *a, **k):
            return self

        def __getattr__(self, name):
            return self

    class _StrategiesModule(types.ModuleType):
        def __getattr__(self, name):
            return _Strategy()

    def _given(*_a, **_k):
        def deco(fn):
            # NB: no functools.wraps — it would set __wrapped__ and pytest
            # would unwrap to the original signature, treating strategy
            # parameters as (missing) fixtures. ``self`` must pass through
            # for methods on test classes.
            def wrapper(*args, **kwargs):
                pytest.skip("hypothesis not installed")
            wrapper.__name__ = getattr(fn, "__name__", "test")
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

    class _Settings:
        """Usable both as ``@settings(...)`` and for profile registration."""

        def __init__(self, *a, **k):
            pass

        def __call__(self, fn):
            return fn

        @staticmethod
        def register_profile(*a, **k):
            pass

        @staticmethod
        def load_profile(*a, **k):
            pass

    class _HealthCheck:
        def __getattr__(self, name):
            return name

    _hyp = types.ModuleType("hypothesis")
    _st = _StrategiesModule("hypothesis.strategies")
    _hyp.given = _given
    _hyp.settings = _Settings
    _hyp.HealthCheck = _HealthCheck()
    _hyp.assume = lambda *a, **k: True
    _hyp.example = lambda *a, **k: (lambda fn: fn)
    _hyp.note = lambda *a, **k: None
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
else:
    settings.register_profile(
        "ci", deadline=None, max_examples=25,
        suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile("ci")


def _interpret_call(dispatch, *args, interpret=None):
    return dispatch(*args, interpret=True)


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Run the Metronome kernel dispatchers in Pallas interpret mode, the
    CPU stand-in for the compiled TPU path that ``backend='kernel'`` takes
    (off a TPU the dispatchers otherwise raise).  Returns the dispatch
    counter, so a test can show that the kernel really ran."""
    from repro.kernels import ops
    for name in ("progressive_fill", "score_multilink",
                 "score_multilink_batch"):
        monkeypatch.setattr(
            ops, name, functools.partial(_interpret_call, getattr(ops, name)))
    return ops.DISPATCHES
