import os

# Tests must see the single real CPU device; keep XLA_FLAGS free of forced
# device counts here.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# hypothesis is an optional extra (`pip install -e .[test]`): property tests
# skip cleanly when it is absent instead of killing collection.
try:
    from hypothesis import HealthCheck, settings
except ModuleNotFoundError:
    pass
else:
    settings.register_profile(
        "repro", max_examples=25, deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large])
    settings.load_profile("repro")
