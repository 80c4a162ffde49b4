"""Kernel dispatch registry: named ops with ref / pallas / pallas_interpret
implementations and automatic backend selection.

Every kernel package registers its implementations on a :class:`KernelOp`
(``kernel_op(name)`` is get-or-create, so registration order never
matters).  Callers go through the op object — ``op(*args, impl=None)`` —
and the registry picks the implementation:

  1. an explicit ``impl=`` argument at the call site (must exist, else
     ``KeyError``),
  2. a process-wide override set with :func:`set_default_impl` (or the
     :func:`use_impl` context manager),
  3. the ``REPRO_KERNEL_IMPL`` environment variable,
  4. backend auto-selection: ``pallas`` on TPU, ``ref`` elsewhere
     (falling back to ``pallas_interpret`` for ops that ship no jnp ref).

Overrides from (2)/(3) that an op does not implement fall through to the
backend default instead of erroring, so ``REPRO_KERNEL_IMPL=pallas`` on a
TPU host is safe even if some op is ref-only.

Besides *implementations* (which backend runs an op), ops can expose
*strategies* — named algorithm knobs within an op that every
implementation honors (e.g. ``lss_topk.dedup`` = ``quadratic`` |
``bitonic``).  A :class:`KernelStrategy` resolves the same way an impl
does — explicit argument > process override (:func:`set_default_strategy`
/ :func:`use_strategy`) > its own env var > an auto-select callback fed
call-site context (e.g. the candidate count) — so shape-dependent
algorithm switches are registry policy, not call-site ``if``\\ s.

Dispatches AND strategy resolutions are recorded at trace time (ops are
typically called inside ``jax.jit``, whose Python body runs once per
compilation), so tests and tooling can assert which implementation and
algorithm actually served a path via :func:`dispatch_log` /
:func:`last_dispatch`.  An op may :func:`record` further choices it
reads off its operands (``lss_topk.slab_layout``).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Callable

import jax

__all__ = [
    "IMPLS", "ENV_VAR", "KernelOp", "kernel_op", "get_op", "list_ops",
    "resolve_impl", "set_default_impl", "use_impl", "dispatch_log",
    "dispatch_count", "dispatch_counts", "last_dispatch",
    "reset_dispatch_log", "record",
    "KernelStrategy", "kernel_strategy", "get_strategy", "list_strategies",
    "set_default_strategy", "use_strategy",
]

IMPLS = ("ref", "pallas", "pallas_interpret")
ENV_VAR = "REPRO_KERNEL_IMPL"

_ops: dict[str, "KernelOp"] = {}
_default_impl: str | None = None
_log: list[tuple[str, str]] = []
_strategies: dict[str, "KernelStrategy"] = {}
_default_strategies: dict[str, str] = {}


class KernelOp:
    """One named op and its registered implementations."""

    def __init__(self, name: str):
        self.name = name
        self.impls: dict[str, Callable] = {}

    def impl(self, impl_name: str) -> Callable:
        """Decorator: register ``fn`` as the ``impl_name`` implementation."""
        def deco(fn: Callable) -> Callable:
            self.register_impl(impl_name, fn)
            return fn
        return deco

    def register_impl(self, impl_name: str, fn: Callable) -> None:
        if impl_name not in IMPLS:
            raise ValueError(
                f"impl must be one of {IMPLS}, got {impl_name!r}")
        self.impls[impl_name] = fn

    def __call__(self, *args, impl: str | None = None, **kwargs):
        choice = resolve_impl(self.name, impl)
        _log.append((self.name, choice))
        return self.impls[choice](*args, **kwargs)

    def __repr__(self) -> str:
        return f"KernelOp({self.name!r}, impls={sorted(self.impls)})"


def kernel_op(name: str) -> KernelOp:
    """Get-or-create the op named ``name``."""
    if name not in _ops:
        _ops[name] = KernelOp(name)
    return _ops[name]


def get_op(name: str) -> KernelOp:
    if name not in _ops:
        raise KeyError(f"unknown kernel op {name!r}; "
                       f"registered: {sorted(_ops)}")
    return _ops[name]


def list_ops() -> list[str]:
    return sorted(_ops)


def set_default_impl(impl: str | None) -> None:
    """Process-wide impl override (``None`` clears it)."""
    global _default_impl
    if impl is not None and impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS} or None, got {impl!r}")
    _default_impl = impl


@contextmanager
def use_impl(impl: str | None):
    """Scoped :func:`set_default_impl`."""
    global _default_impl
    prev = _default_impl
    set_default_impl(impl)
    try:
        yield
    finally:
        _default_impl = prev


def resolve_impl(op_name: str, requested: str | None = None) -> str:
    """Resolve which implementation a call to ``op_name`` should use."""
    op = get_op(op_name)
    if requested is not None:
        if requested not in IMPLS:
            raise ValueError(
                f"impl must be one of {IMPLS}, got {requested!r}")
        if requested not in op.impls:
            raise KeyError(
                f"op {op_name!r} has no {requested!r} impl "
                f"(has: {sorted(op.impls)})")
        return requested
    for choice in (_default_impl, os.environ.get(ENV_VAR) or None):
        if choice is not None:
            if choice not in IMPLS:
                raise ValueError(
                    f"${ENV_VAR} must be one of {IMPLS}, got {choice!r}")
            if choice in op.impls:
                return choice
    if jax.default_backend() == "tpu" and "pallas" in op.impls:
        return "pallas"
    if "ref" in op.impls:
        return "ref"
    if "pallas_interpret" in op.impls:
        return "pallas_interpret"
    raise KeyError(f"op {op_name!r} has no registered impls")


# ----------------------------------------------------------- strategies --

class KernelStrategy:
    """One named algorithm knob shared by every implementation of an op.

    ``choices`` is the closed set of algorithm names; ``env_var`` (if
    given) is a ``REPRO_KERNEL_IMPL``-style per-knob override; ``auto``
    is a callback receiving the call-site context kwargs (e.g.
    ``n_candidates=``) and returning the data-dependent default.
    """

    def __init__(self, name: str, choices: tuple[str, ...],
                 env_var: str | None = None,
                 auto: Callable[..., str] | None = None):
        self.name = name
        self.choices = tuple(choices)
        self.env_var = env_var
        self.auto = auto

    def resolve(self, requested: str | None = None, **ctx) -> str:
        """Resolve which algorithm a call should use; logged like an impl
        dispatch (as ``(strategy_name, choice)``)."""
        choice = None
        if requested is not None:
            self._validate(requested, "explicit strategy")
            choice = requested
        if choice is None:
            override = _default_strategies.get(self.name)
            if override is not None:
                choice = override
        if choice is None and self.env_var:
            env = os.environ.get(self.env_var) or None
            if env is not None:
                self._validate(env, f"${self.env_var}")
                choice = env
        if choice is None and self.auto is not None:
            choice = self.auto(**ctx)
            self._validate(choice, f"{self.name} auto-select")
        if choice is None:
            choice = self.choices[0]
        _log.append((self.name, choice))
        return choice

    def _validate(self, choice: str, source: str) -> None:
        if choice not in self.choices:
            raise ValueError(f"{source} for {self.name!r} must be one of "
                             f"{self.choices}, got {choice!r}")

    def __repr__(self) -> str:
        return f"KernelStrategy({self.name!r}, choices={self.choices})"


def kernel_strategy(name: str, choices: tuple[str, ...] | None = None,
                    env_var: str | None = None,
                    auto: Callable[..., str] | None = None
                    ) -> KernelStrategy:
    """Get-or-create the strategy knob named ``name`` (conventionally
    ``"<op>.<knob>"``)."""
    if name not in _strategies:
        if choices is None:
            raise KeyError(f"unknown kernel strategy {name!r}; "
                           f"registered: {sorted(_strategies)}")
        _strategies[name] = KernelStrategy(name, choices, env_var, auto)
    return _strategies[name]


def get_strategy(name: str) -> KernelStrategy:
    if name not in _strategies:
        raise KeyError(f"unknown kernel strategy {name!r}; "
                       f"registered: {sorted(_strategies)}")
    return _strategies[name]


def list_strategies() -> list[str]:
    return sorted(_strategies)


def set_default_strategy(name: str, choice: str | None) -> None:
    """Process-wide strategy override (``None`` clears it)."""
    strat = get_strategy(name)
    if choice is None:
        _default_strategies.pop(name, None)
        return
    strat._validate(choice, "set_default_strategy")
    _default_strategies[name] = choice


@contextmanager
def use_strategy(name: str, choice: str | None):
    """Scoped :func:`set_default_strategy`."""
    prev = _default_strategies.get(name)
    set_default_strategy(name, choice)
    try:
        yield
    finally:
        set_default_strategy(name, prev)


# ------------------------------------------------------ dispatch records --

def record(name: str, choice: str) -> None:
    """Log a trace-time choice an op made from its operands (e.g.
    ``("lss_topk.slab_layout", "stored")``) beside its dispatches."""
    _log.append((name, choice))


def dispatch_log(start: int = 0) -> tuple[tuple[str, str], ...]:
    """The ``(op_name, impl)`` dispatches since the last reset, in order,
    from the ``start``-th on (pair with :func:`dispatch_count` to copy
    only what a call added).

    Recorded at trace time: a jitted caller contributes one entry per
    compilation, not per device invocation.
    """
    return tuple(_log[start:])


def dispatch_count() -> int:
    """How many dispatches the log holds: a cheap mark to diff against."""
    return len(_log)


def dispatch_counts() -> dict[tuple[str, str], int]:
    counts: dict[tuple[str, str], int] = {}
    for entry in _log:
        counts[entry] = counts.get(entry, 0) + 1
    return counts


def last_dispatch(op_name: str) -> str | None:
    """The impl most recently dispatched for ``op_name`` (None if never)."""
    for name, impl in reversed(_log):
        if name == op_name:
            return impl
    return None


def reset_dispatch_log() -> None:
    _log.clear()
