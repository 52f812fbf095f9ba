"""The named scopes of the serve step, and which one each device op of a
compiled step lies in.

The step runs under ``jax.named_scope``s (DESIGN_OBS.md): ``embed``,
``layer_loop`` (the scan over the blocks), in each block ``attention``
(``proj``, ``kv_cache``, ``core``) and ``ffn`` (for the MoE ``router``,
``dispatch``, ``experts``, ``shared``, ``combine``), and ``lm_head``.
:data:`SCOPES` is that contract; the models open the scopes by these
names.

A scope path is the innermost of the five top-level scopes in an
instruction's ``op_name`` with, below it, the innermost of its own
sub-scopes: so ``attention/core``, ``ffn``, or ``layer_loop`` for what
the loop does outside any block (the per-layer slices and whole-cache
copies).  A fusion whose root carries no ``op_name`` takes that of the
last op fused into it that does work and names a scope, the one nearest
its root.  Any other instruction with
no ``op_name`` was added by the compiler for the op that uses it (a
layout copy, an upcast ahead of a dot), so it takes the scope of its
first user that does device work, passing through tuples,
get-tuple-elements and bitcasts, up to ``INHERIT_STEPS`` deep; failing
that (a copy into the step's result), that of its first operand that
has one, searched the same way; failing that (a prefetch of a weight for
the step's next execution, which XLA adds at the step's end and nothing
uses), that of another user of an operand up to ``INHERIT_STEPS`` above
it.  So the float32 copies of a layer's whole K and V slab, which XLA
adds for the scores and p.v dots, lie in ``attention/core`` as the
contract says, not in the projection or cache write that produced them.

Latent attention (``models/mla.py``) keeps the contract: ``proj`` holds
every weight product (the query, the latent and rope key, the absorption
of ``W_UK`` into the query and of ``W_UV`` into the output, the output
projection) with the latent's norm and RoPE; ``kv_cache`` the new
positions' latents as the cache stores them and their one write into the
cache after the layer loop (with the position count); ``core`` only what
reads the latent slab: scores, softmax and p.c_kv.  The sigmoid router's
selection lies in ``ffn/router``.

:func:`scope_map` reads a compiled step's HLO text (``as_text()``);
:func:`uncovered` lists the device ops it leaves in no scope.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SCOPES = {"embed": (), "layer_loop": (),
          "attention": ("proj", "kv_cache", "core"),
          "ffn": ("router", "dispatch", "experts", "shared", "combine"),
          "lm_head": ()}
UNSCOPED = "(unscoped)"
INHERIT_STEPS = 3
# opcodes that alias a buffer or name a value and run no device work
NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
# computations that run op by op (a fusion's or a reducer's do not)
_STEPPED = re.compile(r"(?:body|condition|true_computation"
                      r"|false_computation)=(%[\w.\-]+)"
                      r"|branch_computations=\{([^}]*)\}")

Instr = Tuple[str, Optional[str], List[str]]    # opcode, op_name, operands


def scope_of(op_name: str) -> Optional[str]:
    """The scope path of one ``op_name``, or None where it names none."""
    path: List[str] = []
    for part in op_name.split("/"):
        if part in SCOPES:
            path = [part]
        elif len(path) == 1 and part in SCOPES[path[0]]:
            path.append(part)
    return "/".join(path) or None


def _balanced(text: str, i: int) -> int:
    """Index just past the parenthesis group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return j + 1
    return len(text)


def instructions(hlo_text: str) -> Dict[str, Instr]:
    """The instructions that run as device ops, those of the entry
    computation and of loop bodies, conditions and branches: name ->
    (opcode, op_name or None, operand names).  A fusion without an
    ``op_name`` gets that of the last op fused into it that does work
    and names a scope."""
    parsed, stepped, comp = {}, set(), None
    scoped: Dict[str, str] = {}     # computation -> last scoped op_name
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(2)
            if c.group(1):
                stepped.add(comp)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        for one, many in _STEPPED.findall(rest):
            stepped.update(_OPERAND.findall(one + many))
        # the result's shape, a tuple in parentheses or one word
        i = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
        rest = rest[i:].lstrip()
        paren = rest.find("(")
        if paren <= 0:
            continue
        operands = _OPERAND.findall(rest[paren:_balanced(rest, paren)])
        op = _OP_NAME.search(rest)
        op_name, opcode = (op.group(1) if op else None), rest[:paren]
        if op_name and opcode not in NO_WORK and scope_of(op_name):
            scoped[comp] = op_name
        calls = _CALLS.search(rest)
        parsed[name] = (comp, opcode, op_name, operands,
                        calls.group(1) if calls else None)
    return {n: (opcode, op_name or scoped.get(callee), operands)
            for n, (c, opcode, op_name, operands, callee) in parsed.items()
            if c in stepped}


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope path, ``UNSCOPED`` where none is found."""
    instrs = instructions(hlo_text)
    users: Dict[str, List[str]] = defaultdict(list)
    for name, (_, _, operands) in instrs.items():
        for o in operands:
            users[o].append(name)

    def search(name: str, steps: int, edges: Callable[[str], List[str]],
               through: Sequence[str]) -> Optional[str]:
        """The first scope along ``edges``, ``steps`` deep; an
        instruction whose opcode is in ``through`` or that has no
        ``op_name`` is passed through."""
        for o in edges(name):
            if o not in instrs:
                continue
            opcode, op_name, _ = instrs[o]
            if op_name is not None and opcode not in through:
                s = scope_of(op_name)
            else:
                s = search(o, steps - 1, edges, through) if steps > 1 \
                    else None
            if s is not None:
                return s
        return None

    def ancestors(name: str, steps: int):
        for o in instrs[name][2]:
            if o in instrs:
                yield o
                if steps > 1:
                    yield from ancestors(o, steps - 1)

    def resolve(name: str) -> Optional[str]:
        op_name = instrs[name][1]
        if op_name is not None:
            return scope_of(op_name)
        return (search(name, INHERIT_STEPS, users.__getitem__, NO_WORK)
                or search(name, INHERIT_STEPS, lambda n: instrs[n][2], ())
                or next(filter(None, (
                    search(a, INHERIT_STEPS, users.__getitem__, NO_WORK)
                    for a in ancestors(name, INHERIT_STEPS))), None))

    return {n: resolve(n) or UNSCOPED for n in instrs}


def uncovered(hlo_text: str) -> List[str]:
    """The instructions that do device work and resolve to no scope."""
    instrs, scopes = instructions(hlo_text), scope_map(hlo_text)
    return sorted(n for n, (opcode, _, _) in instrs.items()
                  if opcode not in NO_WORK and scopes[n] == UNSCOPED)
