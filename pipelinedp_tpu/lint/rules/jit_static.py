"""Jit-staticness: nothing ambient is read at trace time.

A function dispatched through ``instrumented_jit``/``jax.jit`` runs
its Python body ONCE per program signature; everything it reads from
the environment — ``os.environ``, wall-clock ``time.*``, a knob
constant — freezes into the compiled program and silently stops
responding to the env, a test seam or the clock (the shape-blind
knob-read bug PR 9 fixed is this rule's seed fixture).  Values that
must vary pass as (possibly static) arguments; values that must not
vary don't belong in a traced body at all.
"""

from __future__ import annotations

import ast

from pipelinedp_tpu.lint.rules.base import (Rule, subtree_names,
                                            terminal_name)
from pipelinedp_tpu.lint.rules.confinement import NoKnobsRule

_JIT_NAMES = frozenset({"jit", "instrumented_jit"})


def _decorator_is_jit(dec) -> bool:
    if isinstance(dec, ast.Call):
        if terminal_name(dec.func) in _JIT_NAMES:
            return True
        # functools.partial(jax.jit, ...) style
        if terminal_name(dec.func) == "partial":
            return bool(_JIT_NAMES & subtree_names(dec))
        return False
    return terminal_name(dec) in _JIT_NAMES


def _jitted_function_names(tree) -> set:
    """Functions decorated with a jit wrapper, plus functions passed
    by name into ``jax.jit(fn, ...)`` / ``instrumented_jit(fn, ...)``
    assignments (the ``program = instrumented_jit(_kernel, ...)``
    idiom)."""
    jitted = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_decorator_is_jit(d) for d in node.decorator_list):
                jitted.add(node.name)
        elif (isinstance(node, ast.Call)
              and terminal_name(node.func) in _JIT_NAMES
              and node.args
              and isinstance(node.args[0], ast.Name)):
            jitted.add(node.args[0].id)
    return jitted


class JitStaticnessRule(Rule):
    id = "jit-staticness"
    legacy_target = None
    invariant = ("traced bodies read nothing ambient: os.environ, "
                 "time.*, and registered knob constants freeze at "
                 "trace time and stop responding to the env/seam — "
                 "pass them in as (static) arguments instead")
    fix_hint = ("hoist the read to the call site and pass it as an "
                "argument (static_argnames if it shapes the program)")

    def check(self, ctx):
        jitted = _jitted_function_names(ctx.tree)
        if not jitted:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name not in jitted:
                continue
            yield from self._scan_traced_body(node)

    def _scan_traced_body(self, fn):
        for node in ast.walk(fn):
            if node is fn:
                continue
            if isinstance(node, ast.Attribute):
                if (node.attr == "environ"
                        and isinstance(node.value, ast.Name)
                        and node.value.id == "os"):
                    yield (node.lineno,
                           f"os.environ read inside traced "
                           f"'{fn.name}' freezes at trace time")
            if isinstance(node, ast.Call):
                term = terminal_name(node.func)
                recv = (node.func.value
                        if isinstance(node.func, ast.Attribute)
                        else None)
                if term == "getenv" and isinstance(recv, ast.Name) \
                        and recv.id == "os":
                    yield (node.lineno,
                           f"os.getenv inside traced '{fn.name}' "
                           "freezes at trace time")
                elif (isinstance(recv, ast.Name)
                      and recv.id in ("time", "_time")):
                    yield (node.lineno,
                           f"time.{term} inside traced '{fn.name}' "
                           "freezes at trace time")
                elif (term == "value"
                      and isinstance(recv, ast.Name)
                      and recv.id in ("knobs", "_knobs")):
                    # The megasweep contract (ISSUE 18): config values
                    # — batch widths, bounds, eps-splits — reach the
                    # batched kernels as RUNTIME inputs; a knob read
                    # inside the traced body bakes one plan's value
                    # into the compiled program and every new config
                    # batch recompiles.
                    yield (node.lineno,
                           f"knobs.value read inside traced "
                           f"'{fn.name}' freezes the knob's value "
                           "at trace time")
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            if (name in NoKnobsRule.KNOB_CONSTANTS
                    and isinstance(getattr(node, "ctx", None),
                                   ast.Load)):
                yield (node.lineno,
                       f"knob constant {name} read inside traced "
                       f"'{fn.name}' freezes the knob's value")
