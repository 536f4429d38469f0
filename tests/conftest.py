import os

# Tests run on the single real CPU device — only the dry-run forces 512
# placeholder devices, and it does so in its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

try:
    import hypothesis
except ImportError:  # optional dev dependency — property tests skip without it
    hypothesis = None

if hypothesis is not None:
    hypothesis.settings.register_profile(
        "repro",
        max_examples=25,
        deadline=None,
        derandomize=True,
        suppress_health_check=list(hypothesis.HealthCheck),
    )
    hypothesis.settings.load_profile("repro")
