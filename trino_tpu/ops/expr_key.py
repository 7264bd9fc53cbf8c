"""What an expression program is keyed on besides its page's arrays.

``exec/executor.py:expression_program`` runs ``ops/expr_lower.lower`` over
one page as ONE compiled program. Two things the lowering reads on the host
must not make every binding of a statement its own program:

- a literal's VALUE: in the program's expression a literal the lowering only
  computes with holds a :class:`Literal` stand-in, and its value arrives as
  a scalar argument (``LowerCtx.literals``). A lowering that reads the value
  on the host (a LIKE pattern, ``round``'s digits) meets :class:`HostRead`,
  and its caller keeps that literal in the expression as it is;
- a value BOUND (``LoweredVal.bound``: a literal's magnitude, a column's
  ``vrange``), which picks int64 against two-limb arithmetic and becomes a
  projected column's ``vrange``. In the program a bound is a :class:`Sym`:
  arithmetic on it builds a term, and what the lowering DECIDES from it (a
  comparison, or an ``int()``) is a question the program's key must answer.
  A question the key does not answer raises :class:`Unanswered`; the caller
  computes the answer from the exact bounds, adds it and traces again.

So a key holds the answers the lowering asked for along its path, the
coarsest form that leaves the lowering unchanged, and a term left on an
output column is evaluated with the page's exact bounds afterwards: the
column's ``vrange`` is the eager path's to the digit.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import operator
from typing import Dict, Optional

from trino_tpu.data.dictionary import Dictionary

_ANSWERS: contextvars.ContextVar = contextvars.ContextVar(
    "expr_key_answers", default=None)
_TABLES: contextvars.ContextVar = contextvars.ContextVar(
    "expr_key_tables", default=None)


class HostRead(Exception):
    """The lowering read a stand-in literal's value on the host."""

    def __init__(self, index: int):
        super().__init__(f"literal {index} is read on the host")
        self.index = index


class Unfused(Exception):
    """The lowering met arithmetic it keeps out of programs
    (:func:`eager_only`)."""


class Unanswered(Exception):
    """The lowering asked a question that the key does not answer:
    ``question`` is ``("cmp", op, term, term)`` or ``("int", term)`` of a
    bound, or ``("table", name, sources)`` for a table over dictionaries'
    words, which ``build`` (the function, the dictionaries) makes."""

    def __init__(self, question: tuple, build=None):
        super().__init__(f"unanswered: {question!r}")
        self.question = question
        self.build = build


@dataclasses.dataclass(frozen=True)
class Literal:
    """Stands in an ``ir.Constant`` for the value of the program's scalar
    argument ``index``: a literal, or a column-free subtree the caller
    folded, with whether it carries a bound and the codes of the folded
    subtree's deferred errors (none fired). Reading it on the host raises
    :class:`HostRead`."""

    index: int
    bounded: bool = True
    errors: tuple = ()

    def _read(self, *_):
        raise HostRead(self.index)

    __int__ = __index__ = __float__ = __bool__ = __str__ = _read
    __format__ = __abs__ = __neg__ = __lt__ = __le__ = __gt__ = __ge__ = _read
    __add__ = __radd__ = __sub__ = __rsub__ = __mul__ = __rmul__ = _read


def _term(x):
    if isinstance(x, Sym):
        return x.term
    return ("k", operator.index(x))


def _binary(op: str):
    """A term where the other operand is an int or a Sym; with anything
    else (an array, a float) the bound's exact value is asked for."""

    def fn(self, other):
        try:
            return Sym((op, self.term, _term(other)))
        except TypeError:
            return _OPS[op](int(self), other)

    def rfn(self, other):
        try:
            return Sym((op, _term(other), self.term))
        except TypeError:
            return _OPS[op](other, int(self))

    return fn, rfn


def _compare(op: str):
    def fn(self, other):
        try:
            other_term = _term(other)
        except TypeError:
            return _CMP[op](int(self), other)
        return ask(("cmp", op, self.term, other_term))

    return fn


class Sym:
    """A bound inside a traced lowering: a term over the page's exact bounds
    (``("lit", i)``, ``("col", c)``), built by arithmetic and answered, when
    the lowering decides something from it, by the key."""

    __slots__ = ("term",)
    __hash__ = None  # a bound used as a key would hide a decision

    def __init__(self, term: tuple):
        self.term = term

    __add__, __radd__ = _binary("+")
    __sub__, __rsub__ = _binary("-")
    __mul__, __rmul__ = _binary("*")
    __floordiv__, __rfloordiv__ = _binary("//")
    __lt__ = _compare("<")
    __le__ = _compare("<=")
    __gt__ = _compare(">")
    __ge__ = _compare(">=")
    __eq__ = _compare("==")
    __ne__ = _compare("!=")

    def __neg__(self):
        return Sym(("neg", self.term))

    def __abs__(self):
        return Sym(("abs", self.term))

    def __bool__(self):
        return ask(("cmp", "!=", self.term, ("k", 0)))

    def __int__(self):
        return ask(("int", self.term))

    __index__ = __int__

    def __repr__(self):
        return f"Sym{self.term!r}"


def ask(question: tuple):
    """The key's answer to ``question``; :class:`Unanswered` without one."""
    answers = _ANSWERS.get()
    if answers is None or question not in answers:
        raise Unanswered(question)
    return answers[question]


def eager_only(what: str) -> None:
    """Raise :class:`Unfused` inside the trace of an expression program
    (:func:`answering`): ``what`` unrolls into some hundred primitives a
    row (int64 division emulated on the TPU, int128 long division), over
    which the TPU compiler takes tens of seconds to minutes once they are
    fused into one program, where the eager tier compiles each primitive
    alone in a fraction of a second. Elsewhere (the eager tier, the
    compiled tiers, the host's folding of literals) nothing happens.

    The kernels call it, and not the seam that builds the program, because
    only the lowering knows whether it reaches them: many calls reach the
    calendar (``extract``, ``date_trunc``, month arithmetic, formatting),
    a decimal division reaches the long division only where its bounds
    leave it two-limb (a question the key answers while tracing), and a
    subtree over literals alone that reaches either is folded on the host
    before any trace (exec/executor.py ``_folded``). A list of calls in the
    seam would repeat each lowering's choices."""
    if _ANSWERS.get() is not None:
        raise Unfused(what)


def table(name: tuple, build, dictionaries: tuple):
    """The program's argument that stands for ``build(*dictionaries)``, a
    table over the words of dictionaries some of which are stand-ins for
    the page's own (exec/page_tree.py ``InputDictionary``: a channel and a
    size, no words); :class:`Unanswered` where the key has none."""
    question = ("table", name, tuple(
        ("words", tuple(d.values)) if isinstance(d, Dictionary)
        else ("channel", d.channel) for d in dictionaries))
    tables = _TABLES.get()
    if tables is None or question not in tables:
        raise Unanswered(question, (build, dictionaries))
    return tables[question]


@contextlib.contextmanager
def answering(answers: Dict[tuple, object], tables: Dict[tuple, object]):
    """Answer the questions a lowering asks of its bounds from
    ``answers``, and hand it the tables over its page's dictionaries from
    ``tables``, while the block runs (the trace of one program)."""
    token, tables_token = _ANSWERS.set(answers), _TABLES.set(tables)
    try:
        yield
    finally:
        _ANSWERS.reset(token)
        _TABLES.reset(tables_token)


_OPS = {"+": operator.add, "-": operator.sub, "*": operator.mul,
        "//": operator.floordiv}
_CMP = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
        ">=": operator.ge, "==": operator.eq, "!=": operator.ne}


def evaluate(term: tuple, env: Dict[tuple, int]) -> int:
    """``term`` with the exact bounds ``env`` (``("lit", i)`` / ``("col",
    c)`` -> int) put in."""
    kind = term[0]
    if kind == "k":
        return term[1]
    if kind in ("lit", "col"):
        return env[term]
    if kind == "neg":
        return -evaluate(term[1], env)
    if kind == "abs":
        return abs(evaluate(term[1], env))
    return _OPS[kind](evaluate(term[1], env), evaluate(term[2], env))


def answer(question: tuple, env: Dict[tuple, int]):
    """What the eager lowering decides where it asks ``question`` with the
    exact bounds ``env``."""
    if question[0] == "int":
        return evaluate(question[1], env)
    _, op, a, b = question
    return _CMP[op](evaluate(a, env), evaluate(b, env))


def term_of(bound) -> Optional[tuple]:
    """A bound the program hands back (an int, a Sym, or None) as a term."""
    if bound is None:
        return None
    return _term(bound)


def vrange_bound(vrange) -> int:
    """|value| bound of a column's ``vrange``: inside a traced program the
    column carries its bound's :class:`Sym` in place of the pair."""
    if isinstance(vrange, Sym):
        return vrange
    return max(abs(int(vrange[0])), abs(int(vrange[1])))
