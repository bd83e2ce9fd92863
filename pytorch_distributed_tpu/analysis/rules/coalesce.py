"""``uncoalesced-collective`` — one eager collective per tree leaf.

A loop over ``tree_flatten``/``tree_leaves`` output that issues an eager
collective (``pg.all_reduce(leaf)``, ...) per leaf pays one full DCN/ICI
round trip — launch latency, small-message bandwidth, one host sync —
*per parameter tensor*. A GPT-2 has hundreds of leaves; the coalesced
form (flatten once, bucket or stack the leaves, one collective, unflatten
— what ``broadcast_coalesced`` and the bucketed DDP reducers do) is an
order of magnitude cheaper. In-jit collectives (``lax.psum`` under ``jit``/
``shard_map``) are exempt: XLA fuses those across leaves by itself.

The rule fires only when the loop demonstrably iterates tree leaves (a
direct ``tree_leaves``/``tree_flatten`` iterator, or a name assigned from
one in the same file) AND the per-iteration collective consumes the loop
variable — so a loop that merely logs leaf shapes, or a collective on
something else inside the loop, stays quiet.
"""

from __future__ import annotations

import ast
from typing import Iterator, Set

from pytorch_distributed_tpu.analysis.core import (
    Finding, Module, Rule, register,
)

#: eager collective method/function names (ProcessGroup verbs). P2P
#: send/recv are excluded: per-leaf pipelining can be intentional.
_EAGER_COLLECTIVES = {
    "all_reduce", "all_gather", "reduce_scatter", "broadcast",
    "reduce", "gather", "scatter", "all_to_all",
}

#: names whose call output IS a leaf list
_LEAVES_NAMES_ = {"tree_leaves", "tree_leaves_with_path"}
#: names returning a (leaves, treedef) pair — leaves via [0] / unpacking
_FLATTEN_NAMES = {"tree_flatten", "tree_flatten_with_path"}

#: in-jit / array-library namespaces whose same-named ops XLA coalesces
_JIT_NAMESPACES = ("jax", "jnp", "lax", "np", "numpy")


def _is_leaves_expr(module: Module, node: ast.AST) -> bool:
    """Does this expression evaluate to a tree-leaf list?

    ``tree_leaves(x)``, ``jax.tree.leaves(x)``, ``tree_flatten(x)[0]``.
    """
    if isinstance(node, ast.Subscript):
        return _is_flatten_call(module, node.value)
    if isinstance(node, ast.Call):
        qual = module.resolve(node.func) or ""
        return (qual.split(".")[-1] in _LEAVES_NAMES_
                or qual == "jax.tree.leaves")
    return False


def _is_flatten_call(module: Module, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    qual = module.resolve(node.func) or ""
    return (qual.split(".")[-1] in ("tree_flatten", "tree_flatten_with_path")
            or qual == "jax.tree.flatten")


def _leaves_names(module: Module) -> Set[str]:
    """Names assigned from a leaves expression anywhere in the file:
    ``leaves = tree_leaves(p)``, ``leaves, treedef = tree_flatten(p)``."""
    names: Set[str] = set()
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if isinstance(tgt, ast.Name) and _is_leaves_expr(module, node.value):
            names.add(tgt.id)
        elif (isinstance(tgt, ast.Tuple) and tgt.elts
                and isinstance(tgt.elts[0], ast.Name)
                and _is_flatten_call(module, node.value)):
            # leaves, treedef = tree_flatten(x): first element is the list
            names.add(tgt.elts[0].id)
    return names


def _target_names(target: ast.AST) -> Set[str]:
    """Loop-variable names, including ``for path, leaf in ...`` tuples."""
    if isinstance(target, ast.Name):
        return {target.id}
    if isinstance(target, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for el in target.elts:
            out |= _target_names(el)
        return out
    return set()


def _iterates_leaves(module: Module, it: ast.AST, leaf_names: Set[str]) -> bool:
    if _is_leaves_expr(module, it):
        return True
    if isinstance(it, ast.Name) and it.id in leaf_names:
        return True
    # enumerate(leaves) / zip(leaves, ...) keep leaf iteration
    if isinstance(it, ast.Call):
        qual = module.resolve(it.func) or ""
        if qual in ("enumerate", "zip", "reversed"):
            return any(
                _iterates_leaves(module, a, leaf_names) for a in it.args
            )
    return False


def _collective_calls(module: Module, body_nodes, loop_vars: Set[str]):
    """Eager collective calls in the loop body that consume a loop var."""
    for stmt in body_nodes:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute):
                verb = node.func.attr
            elif isinstance(node.func, ast.Name):
                verb = node.func.id
            else:
                continue
            if verb not in _EAGER_COLLECTIVES:
                continue
            qual = module.resolve(node.func) or ""
            if qual.split(".", 1)[0] in _JIT_NAMESPACES:
                continue  # lax.psum-family under jit: XLA coalesces
            arg_names = {
                n.id
                for a in list(node.args) + [kw.value for kw in node.keywords]
                for n in ast.walk(a) if isinstance(n, ast.Name)
            }
            if arg_names & loop_vars:
                yield node, verb


@register
class UncoalescedCollective(Rule):
    name = "uncoalesced-collective"
    description = (
        "loop over tree_flatten leaves issuing one eager collective per "
        "leaf — one DCN round trip per tensor; coalesce into one call"
    )

    def check(self, module: Module) -> Iterator[Finding]:
        leaf_names = _leaves_names(module)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.For):
                if not _iterates_leaves(module, node.iter, leaf_names):
                    continue
                loop_vars = _target_names(node.target)
                for call, verb in _collective_calls(
                        module, node.body, loop_vars):
                    yield module.finding(
                        self.name, call,
                        f"eager {verb}() issued per tree leaf in this "
                        f"loop — each call is a separate DCN/ICI round "
                        f"trip; flatten once, coalesce the leaves "
                        f"(stack/bucket or a *_coalesced op), and issue "
                        f"one collective",
                    )
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.GeneratorExp)):
                loop_vars: Set[str] = set()
                leafy = False
                for gen in node.generators:
                    if _iterates_leaves(module, gen.iter, leaf_names):
                        leafy = True
                        loop_vars |= _target_names(gen.target)
                if not leafy:
                    continue
                for call, verb in _collective_calls(
                        module, [node.elt], loop_vars):
                    yield module.finding(
                        self.name, call,
                        f"eager {verb}() mapped over tree leaves in this "
                        f"comprehension — one DCN/ICI round trip per "
                        f"leaf; coalesce the flattened leaves and issue "
                        f"one collective",
                    )
