"""Optional-hypothesis shim.

``hypothesis`` is a dev-only dependency (requirements-dev.txt).  When it is
missing the property tests must skip -- not abort the whole suite at
collection time -- so import ``given``/``settings``/``st`` from here instead
of from ``hypothesis`` directly.
"""
try:
    from hypothesis import example, given, settings  # noqa: F401
    from hypothesis import strategies as st  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised only without hypothesis
    import pytest

    HAVE_HYPOTHESIS = False

    class _AnyStrategy:
        """Stands in for ``strategies``: every attribute/call returns self."""

        def __getattr__(self, name):
            return self

        def __call__(self, *args, **kwargs):
            return self

    st = _AnyStrategy()

    def settings(*args, **kwargs):
        return lambda fn: fn

    def example(*args, **kwargs):
        return lambda fn: fn

    def given(*args, **kwargs):
        return pytest.mark.skip(reason="hypothesis not installed")
