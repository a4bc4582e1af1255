from . import bits  # noqa: F401
from .config import Config, load_config  # noqa: F401


def effective_platform() -> str:
    """Platform of the EFFECTIVE default device: honors an active
    ``jax.default_device(...)`` context (which the test suite uses to pin
    compile-bound tests to the host) before falling back to the process
    default backend.  The single source of truth for engine selection —
    ops/ibdcf.best_engine and protocol/collect._expand_engine both route
    through here, so a platform-string quirk is fixed in one place."""
    import jax

    dd = jax.config.jax_default_device
    if dd is not None:
        return getattr(dd, "platform", dd)
    return jax.default_backend()


def require_accelerator(backend: str) -> None:
    """Exit unless the configured aggregation backend resolved: with
    ``backend: "tpu"`` (the config default) a process that found no
    accelerator would otherwise carry on on XLA:CPU and look like a very
    slow chip.  ``backend: "cpu"`` is the explicit opt-out."""
    import jax

    if backend != "cpu" and jax.default_backend() == "cpu":
        raise SystemExit(
            f'config says backend: "{backend}" but JAX resolved no '
            'accelerator (default backend is "cpu"); to run on the host on '
            'purpose set "backend": "cpu" in the config'
        )
