"""Operations and bytes the algorithm needs, computed from the
configuration's file and the client's own record of the traffic — never
from what a kernel happened to do.  Kept with the benchmark.

The five counts are an architecture's, so each call goes to the file
named by the configuration's published ``model_type``,
``arch/<model_type>.py`` beside this one: found by path like a metric's
reader, with no registry and no default.  The same file holds that
architecture's plain forward (``reference.py``)."""

from __future__ import annotations

import functools
import importlib.util
import os

ARCH_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "arch")


def arch_path(cfg: dict, arch_dir: str = ARCH_DIR) -> str:
    """The file of this configuration's architecture; ValueError, with
    the path looked for, where the configuration names none or the file
    is not there."""
    name = cfg.get("model_type")
    if not name or not isinstance(name, str):
        raise ValueError(
            f"the configuration {cfg.get('name')!r} has no model_type: its "
            f"reference forward and work counts are looked for at "
            f"{os.path.join(arch_dir, '<model_type>.py')}")
    path = os.path.join(arch_dir, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"model_type {name!r} of the configuration "
                         f"{cfg.get('name')!r} names no file: looked for {path}")
    return path


@functools.lru_cache(maxsize=None)  # a file is executed once a process
def load_arch(path: str):
    name = "arch_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _arch(cfg: dict):
    return load_arch(arch_path(cfg))


def matmul_params(cfg: dict) -> int:
    """Parameters every token is multiplied through."""
    return _arch(cfg).matmul_params(cfg)


def token_flops(cfg: dict, context: int, with_head: bool = True) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions."""
    return _arch(cfg).token_flops(cfg, context, with_head)


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """Forward FLOPs of the prefill of a whole prompt."""
    return _arch(cfg).prompt_flops(cfg, prompt_len)


def kv_bytes_per_position(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes of cached state one position holds, all layers."""
    return _arch(cfg).kv_bytes_per_position(cfg, kv_dtype_bytes)


def decode_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    """Bytes of cache that decoding one token at each of ``contexts``
    must read at the least."""
    return _arch(cfg).decode_kv_bytes(cfg, contexts)
