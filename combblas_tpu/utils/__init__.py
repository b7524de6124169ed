"""Host-side utilities: I/O helpers, the R-MAT generator, checkpoints,
the compile-cache switch."""


def inherited_platform() -> str:
    """First entry of the ``JAX_PLATFORMS`` this process was launched
    under, lower-cased; ``""`` when unset (JAX then takes the
    accelerator if the machine has one).  Device choice is deployment
    configuration: launchers and benches read it here and never assign
    it."""
    import os

    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()


def device_fields() -> dict:
    """The device a result ran on, as JAX reports it — every JSON line
    a bench or smoke prints carries these, so a number measured on the
    CPU can never be read as a chip number.  Initialises the backend:
    a router that must hold no chip calls it only after its children
    are gone."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
    }
