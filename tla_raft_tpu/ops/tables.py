"""A successor kernel's device tables as one program operand.

The kernel's guard masks, coefficient matrices and hash planes are
device arrays built once per config (``ops/successor.get_kernel``).  A
jitted program that read them as attributes would close over them, and
JAX lowers every captured array as a ``stablehlo.constant`` literal: at
S=5 each program would carry ~38.6 MB of literals into lowering, into
the XLA compile and into its persistent-cache entry.  Instead every
program takes the kernel's ``KernelTables`` as an explicit operand and
traces against a *view* of the kernel whose arrays are that operand's
leaves (``SuccessorKernel.bind``).  The tables stay on the device once
per kernel; an operand adds no copy.
"""

from __future__ import annotations

import copy
import inspect
import weakref
from typing import NamedTuple

import jax


class KernelTables(NamedTuple):
    """Every device array one successor kernel owns, by owner.

    Each field maps an attribute name of its owner to the array (or
    list of arrays) it holds: ``kern`` the ``SuccessorKernel``, ``guard``
    its ``GuardTables``, ``mxu`` the ``MXUTables`` (empty off the MXU
    path), ``dense`` the ``DenseExpand``, ``fpr`` the ``Fingerprinter``.
    ``orbit`` holds the fingerprinter's canonical-relabel tables for the
    checkers that run orbit pruning, else None."""

    kern: dict
    guard: dict
    mxu: dict
    dense: dict
    fpr: dict
    orbit: dict | None = None


def owned(obj) -> dict:
    """The device arrays ``obj`` holds as attributes (lists of arrays
    too), by attribute name."""
    out = {}
    for name, value in vars(obj).items():
        if isinstance(value, jax.Array):
            out[name] = value
        elif (isinstance(value, list) and value
              and all(isinstance(x, jax.Array) for x in value)):
            out[name] = value
    return out


def view(obj, arrays: dict, **attrs):
    """A shallow copy of ``obj`` whose attributes ``arrays`` and
    ``attrs`` name are replaced: what a traced program reads in place
    of the owner.  The owner itself is never changed, so programs
    traced on other threads see it as it was."""
    v = copy.copy(obj)
    vars(v).update(arrays)
    vars(v).update(attrs)
    return v


def bound(owner, tables: KernelTables):
    """``owner`` (a checker, a ``LevelSpec``, a bucket's programs) as a
    traced program sees it: a view whose ``kern`` and ``fpr`` read their
    device tables from ``tables``, the program's operand."""
    kern = owner.kern.bind(tables)
    return view(owner, {}, kern=kern, fpr=kern.fpr)


def nbytes(tables: KernelTables) -> int:
    """Bytes a program receives as its ``tables`` operand."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tables))


class TabledProgram:
    """A jitted ``fn(tables, *args)`` that is called as ``fn(*args)``.

    Holds the kernel's tables and passes them as the first operand, so
    callers, the AOT prewarm (``.lower``) and the cost probes use the
    program exactly as they used a program that closed over them."""

    def __init__(self, fn, tables: KernelTables, **jit_kwargs):
        self.jitted = jax.jit(fn, **jit_kwargs)
        self.tables = tables
        self.table_bytes = nbytes(tables)

    def __call__(self, *args, **kwargs):
        return self.jitted(self.tables, *args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.jitted.lower(self.tables, *args, **kwargs)


def method_program(method, tables: KernelTables, wrap=None,
                   **jit_kwargs) -> TabledProgram:
    """A bound ``method`` as a jitted program over ``tables``.

    The program traces the method on its instance ``bound`` to the
    tables, so no table is captured as a literal; ``wrap`` (e.g. a
    ``shard_map``) is applied before the jit.  The program holds the
    instance weakly: owners keep their programs as attributes, and a
    strong reference would be a cycle (owner -> program -> method ->
    owner), so a finished checker, and the device buffers it holds,
    would outlive its last user until the cyclic collector happened to
    run.  Name and signature follow
    ``method`` with ``tables`` in front (jit's static argument names and
    program names read them)."""
    ref = weakref.ref(method.__self__)
    name = method.__name__

    def call(tables, *args, **kwargs):
        return getattr(bound(ref(), tables), name)(*args, **kwargs)

    sig = inspect.signature(method)
    call.__name__ = name
    call.__qualname__ = method.__qualname__
    call.__signature__ = sig.replace(parameters=[
        inspect.Parameter("tables", inspect.Parameter.POSITIONAL_OR_KEYWORD),
        *sig.parameters.values(),
    ])
    fn = call if wrap is None else wrap(call)
    return TabledProgram(fn, tables, **jit_kwargs)
