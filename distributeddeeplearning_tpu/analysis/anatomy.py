"""Step anatomy: which part of the program a compiled instruction belongs to.

A device trace names an operation by its HLO instruction (``%fusion.3884``),
which says nothing about what it computes. The compiled step's own HLO text
does: every instruction carries ``metadata={op_name="..."}``, the jax name
stack it was traced under — ``jvp(...)`` / ``transpose(...)`` for the pass,
the flax module path, every ``jax.named_scope`` (train/steps.py's
``STEP_SCOPES`` and ``LOSS_SCOPE``, models/gpt.py's ``embed`` / ``mlp`` /
``head``, models/afmoe.py's ``attn_window`` / ``attn_full``,
models/kimi_linear.py's ``attn_kda`` / ``attn_mla``,
models/hyper_connections.py's ``mhc``, models/moe.py's
``moe_router`` / ``moe_dispatch`` / ``moe_experts`` / ``moe_combine``) and
every Pallas kernel's ``name`` (ops/pallas.py). This module reads those names
back:

- :func:`table` maps each instruction of an HLO module text to its
  ``op_name``;
- :func:`part_of` maps an ``op_name`` to ``(phase, part)``;
- :func:`by_part` sums a trace's time per operation by the two.

The vocabulary of parts and the rule that assigns them live here and nowhere
else. Pure stdlib, like ``analysis/collectives.py``: importing it never
imports jax.
"""
from __future__ import annotations

import re

PHASES = ("forward", "backward", "update")

# Every part a step's device time is booked under. ``unattributed`` is what
# no rule placed (instructions the compiler made without metadata, scopes the
# rule does not know — a CNN's convolutions, today).
PARTS = ("flash_fwd", "flash_dq", "flash_dkv", "attention_window",
         "attention_full", "attention_kda", "attention_mla", "residual_mhc",
         "moe_routing", "moe_experts", "attention_other",
         "mlp", "layernorm", "embed", "head", "loss", "remat", "loss_scale",
         "optimizer", "ema_guard", "grad_reduce", "unattributed")

_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
# Scopes a model puts round its own parts -> part. A model whose layers
# differ in attention kind scopes the kernel calls by kind, and the three
# kernels then book under the kind (so do a delta-rule layer's convolutions,
# gates, chunked scan and gated norm, which have no kernel of their own
# name); one with routed experts has the router,
# the sort, the gathers and the weighted sum as ``moe_routing`` and the
# grouped products as ``moe_experts``; one whose residual is several streams
# has everything its hyper-connections add (the norm over the streams, the
# product that makes the coefficients, Sinkhorn's iterations and the three
# mixes) as ``residual_mhc``.
_MODEL_SCOPES = {"attn_window": "attention_window",
                 "attn_full": "attention_full",
                 "attn_kda": "attention_kda", "attn_mla": "attention_mla",
                 "mhc": "residual_mhc",
                 "moe_router": "moe_routing", "moe_dispatch": "moe_routing",
                 "moe_combine": "moe_routing", "moe_experts": "moe_experts"}
# The TPU compiler turns ``jax.lax.ragged_dot`` into kernels of its own and
# names them itself (``ragged-dot-none``, ``ragged-dot-metadata``): the name
# stack is gone. ``table`` gives such an instruction its operands' name stack
# with this scope at the end: a backward operand's, if it has one (the
# product is then part of the backward pass), else the first that has one.
_COMPILER_NAMED = "ragged-dot"
_RAGGED_SCOPE = "moe_experts"
# A ``conditional`` runs the instructions of one of its branches, and a device
# trace holds it as an operation of its own that spans theirs (seen on the
# chip, PR 28: a step's operations add up to its busy time only without the
# routed experts' conditionals). ``table`` puts this before its ``op_name``
# (which still says what it belongs to), and ``by_part`` leaves it out: its
# time is its branch's. A ``while`` (a ``lax.scan``: the chunked delta rule's
# loops) is held the same way, spanning every turn of its body, and is left
# out the same way.
SPANS_ITS_BRANCH = "(conditional: its time is its branch's operations')"
_SPANNING_OPCODES = ("conditional", "while")
# train/steps.py STEP_SCOPES outside ``grads`` -> part; all are phase update.
_UPDATE_SCOPES = {"grad_reduce": "grad_reduce", "loss_scale": "loss_scale",
                  "optimizer": "optimizer", "ema": "ema_guard",
                  "guard": "ema_guard"}
_LAYERNORM = re.compile(r"^(ln_?\w*|\w*layer_?norm\w*)$", re.IGNORECASE)
_BLOCK = re.compile(r"^layers?_?\d+$")
# jax names the boundary of a recomputed block ``remat`` / ``remat2`` /
# ``checkpoint``; what is booked there and nowhere inside the block is the
# boundary's own: copies and relayouts of the saved block inputs.
_REMAT = re.compile(r"^(remat\d*|checkpoint)$")

# "  ROOT %name = <shape> opcode(" — the shape is one token or a
# parenthesised tuple, and may hold layout braces and memory-space
# annotations; the opcode is the first bare word followed by "(" after it.
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_LEADING_NAME = re.compile(r"%?([\w.\-]*)")


def _balanced(text: str) -> int:
    """Index just past the parenthesis that closes ``text[0] == "("``."""
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i + 1
    return len(text)


def _opcode_and_operands(rest: str) -> tuple[str, list[str]]:
    """Opcode and operand names of an instruction, from what follows
    ``" = "``: a shape (one token, or a parenthesised tuple), the opcode, the
    parenthesised operands."""
    body = (rest[_balanced(rest):] if rest.startswith("(")
            else rest.partition(" ")[2])
    m = _OPCODE.match(body)
    if m is None:
        return "", []
    operands = body[m.end() - 1:]
    return m.group(1), _OPERAND.findall(operands[:_balanced(operands)])


def table(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` over every computation of the module.

    An instruction that carries no ``op_name`` of its own takes the one that
    explains it: a fusion (anything with ``calls=``) its called
    computation's root's; any other (the copies, bitcasts and tuples the
    compiler inserts) its first operand's that has one, or, where it only
    reads parameters, its first user's, which a chain of such instructions
    (the asynchronous copy of a ``conditional`` branch's argument: element
    of the tuple, start, done) hands down to its beginning. HLO text lists
    callees before callers and operands before users, so one pass resolves
    all three.
    Parameters keep no name: an argument's path names no part of the
    program. A ``conditional`` or a ``while`` explains its neighbours like
    any other instruction and has :data:`SPANS_ITS_BRANCH` put before its
    own name. Instructions
    nothing explains are left out. Tolerant of torn
    text: a line that does not parse is skipped.
    """
    names: dict[str, str] = {}
    roots: dict[str, str] = {}     # computation -> its root's op_name
    unexplained: dict[str, list[str]] = {}  # waiting for a user: operands
    spanning: list[str] = []       # conditionals and whiles
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        is_root, name = bool(m.group(1)), m.group(2)
        opcode, operands = _opcode_and_operands(line[m.end():])
        if opcode == "parameter":
            continue
        meta = _OP_NAME.search(line)
        if meta is not None and meta.group(1).startswith(_COMPILER_NAMED):
            known = [names[o] for o in operands if o in names]
            op_name = next((n for n in known if "transpose(" in n),
                           known[0] if known else None)
            if op_name is not None:
                op_name += "/" + _RAGGED_SCOPE
        elif meta is not None:
            op_name = meta.group(1).replace('\\"', '"').replace("\\'", "'")
        else:
            called = _CALLS.search(line)
            op_name = roots.get(called.group(1)) if called else None
            if op_name is None:
                op_name = next((names[o] for o in operands if o in names),
                               None)
        if opcode in _SPANNING_OPCODES:  # named or not: never booked
            spanning.append(name)
        if op_name is None:
            unexplained[name] = operands
            continue
        names[name] = op_name
        while operands:
            operand = operands.pop()
            if operand in unexplained:
                names[operand] = op_name
                operands.extend(unexplained.pop(operand))
        if is_root and computation is not None:
            roots[computation] = op_name
    names.update({name: SPANS_ITS_BRANCH + names.get(name, "")
                  for name in spanning})
    return names


def part_of(op_name: str) -> tuple[str, str]:
    """``(phase, part)`` of one ``op_name``; ``part`` is one of :data:`PARTS`,
    ``phase`` one of :data:`PHASES`.

    The name stack is split into its scopes (``jit(step_fn)/grads/
    transpose(jvp(GptLM))/layer3/ln1/mul`` -> jit, step_fn, grads, transpose,
    jvp, GptLM, layer3, ln1, mul). Phase: an update scope of the step makes
    it ``update``; else ``transpose`` (the linear transpose of the forward
    pass, and a custom-vjp backward rule, which jax names
    ``transpose(...)/jvp(...)``) makes it ``backward``; else ``jvp`` or the
    ``grads`` scope (the forward's random keys are not differentiated) makes
    it ``forward``; what is outside every scope of the step (its key
    fold-in, the step counter) is ``update``. Part, first match: an update
    scope; a model's own scope (``_MODEL_SCOPES``: attention by layer kind,
    routed experts, hyper-connections); a flash kernel's name; ``loss`` / ``head`` / ``embed``; a
    LayerNorm module; the ``mlp`` scope or an ``mlp*`` module; anything else
    inside an attention module or bare in a decoder block (the attention
    half's dropout and residual) is ``attention_other``; what only the
    boundary of a recomputed block names is ``remat``.
    """
    scopes = [s for s in re.split(r"[/()]", op_name) if s]
    have = set(scopes)
    update = next((s for s in scopes if s in _UPDATE_SCOPES), None)
    if update is not None:
        return "update", _UPDATE_SCOPES[update]
    if "transpose(" in op_name:  # the transform, not the array primitive
        phase = "backward"
    elif "jvp(" in op_name or "grads" in have:
        phase = "forward"
    else:
        phase = "update"
    model = next((s for s in reversed(scopes) if s in _MODEL_SCOPES), None)
    if model is not None:  # the innermost: ragged products end in theirs
        return phase, _MODEL_SCOPES[model]
    for kernel in _KERNELS:
        if kernel in have:
            return phase, kernel
    for part in ("loss", "head", "embed"):
        if part in have:
            return phase, part
    if any(_LAYERNORM.match(s) for s in scopes):
        return phase, "layernorm"
    if any(s.startswith("mlp") for s in scopes):
        return phase, "mlp"
    if any("attention" in s.lower() or _BLOCK.match(s) for s in scopes):
        return phase, "attention_other"
    if any(_REMAT.match(s) for s in scopes):
        return phase, "remat"
    return phase, "unattributed"


def by_part(durations: dict[str, float], table: dict[str, str]
            ) -> dict[tuple[str, str], float]:
    """``{(phase, part): time}`` of a trace's ``{operation: time}``. A trace
    names an operation by its instruction or by its whole HLO line, which
    starts with it (``%fusion.7 = ...``). An instruction that ``table`` does
    not hold has no phase (``"-"``) and the part ``unattributed``; a
    ``conditional`` or ``while`` is left out, its branch's or body's
    operations being in the trace themselves."""
    out: dict[tuple[str, str], float] = {}
    for line, time in durations.items():
        op_name = table.get(_LEADING_NAME.match(line).group(1))
        if op_name and op_name.startswith(SPANS_ITS_BRANCH):
            continue
        key = part_of(op_name) if op_name else ("-", "unattributed")
        out[key] = out.get(key, 0.0) + time
    return out
