"""Work counts, one file per configuration (``<config>.py``), found by
the configuration's name."""
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def for_config(name: str):
    """The work module of configuration ``name``."""
    path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_work_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
