"""Plain float32 references, written from the published descriptions.
They import nothing from ``ray_tpu.models`` or ``ray_tpu.ops``; the
parameter pytree (names and shapes) is data the system hands over."""
import importlib


def load(name: str):
    """The reference module a configuration file names."""
    return importlib.import_module(f"{__name__}.{name}")
