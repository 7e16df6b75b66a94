"""Operations and bytes counted from shapes: one file per kernel, and one
per configuration for the model flops of a served request."""

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(name: str):
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no cost file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_cost_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
