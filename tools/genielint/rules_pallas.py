"""Rule: pallas-kernel-contract.

Every `pl.pallas_call` in `kernels/` must satisfy three statically-checkable
contracts (the FLASH lesson: a tile/dtype mismatch in a fused kernel
corrupts counts silently, it does not crash):

  1. index-map arity == grid rank for every BlockSpec -- a missing/extra
     grid index silently replays or skips tiles.
  2. estimated VMEM tile footprint (sum over in/out specs of
     prod(block dims) x dtype bytes) stays under the configurable budget
     (--vmem-budget-mb).  Dims are folded from module constants, parameter
     defaults, and local shape math; a `None` dim is squeezed (size 1).
     The dims of one block that cannot be folded (e.g. the data-dependent
     signature width m) together assume `config.assume_dim`: a grouped
     layout such as (Mp/G, TQ, G) splits one width over two dims.  The
     estimate sees neither lane padding nor in-kernel temporaries, so it
     is a screen only: tests/test_tpu_compile.py compiles every kernel for
     the chip, and the TPU compiler decides VMEM (docs/CONTRACTS.md).
  3. out_shape dtypes match the MatchModel registry's count-dtype policy
     (exact int32 accumulation; narrowing happens post-kernel via
     as_count_dtype).  A float out_shape reintroduces the 2^24 rounding
     bound PR 6 removed from the cosine kernel.

Also checked: the number of in_specs matches the number of operands the
pallas_call is applied to.
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from tools.genielint.config import LintConfig
from tools.genielint.core import (Finding, LintModule, call_name,
                                  const_resolver, dotted_name, parent_map,
                                  register)

RULE = "pallas-kernel-contract"

_DTYPE_BYTES = {
    "int8": 1, "uint8": 1, "bool_": 1,
    "int16": 2, "uint16": 2, "float16": 2, "bfloat16": 2,
    "int32": 4, "uint32": 4, "float32": 4,
    "int64": 8, "uint64": 8, "float64": 8,
}
_FALLBACK_BYTES = 4  # unknown operand dtype: assume a full 4-byte lane


def _module_env(tree: ast.Module) -> dict:
    env: dict[str, int] = {}
    resolve = const_resolver(env)
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            val = resolve(node.value)
            if val is not None:
                env[node.targets[0].id] = val
    return env


def _fn_env(fn: ast.FunctionDef, module_env: dict) -> tuple[dict, dict]:
    """(int env, local tuple assignments) for one kernel-builder function."""
    env = dict(module_env)
    resolve = const_resolver(env)
    args = fn.args
    pos = args.posonlyargs + args.args
    for arg, default in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        val = resolve(default)
        if val is not None:
            env[arg.arg] = val
    for arg, default in zip(args.kwonlyargs, args.kw_defaults):
        if default is not None:
            val = resolve(default)
            if val is not None:
                env[arg.arg] = val
    tuples: dict[str, ast.Tuple] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
            if isinstance(node.value, (ast.Tuple, ast.List)):
                tuples[name] = node.value
            else:
                val = resolve(node.value)
                if val is not None:
                    env[name] = val
    return env, tuples


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _as_sequence(node: ast.AST, tuples: dict) -> list[ast.AST]:
    if isinstance(node, ast.Name) and node.id in tuples:
        node = tuples[node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        return list(node.elts)
    return [node]


def _blockspecs(node: Optional[ast.AST], tuples: dict) -> list[ast.Call]:
    if node is None:
        return []
    return [el for el in _as_sequence(node, tuples)
            if isinstance(el, ast.Call) and call_name(el) == "BlockSpec"]


def _dtype_name(node: Optional[ast.AST]) -> Optional[str]:
    """jnp.int32 / np.float32 / "int32" -> "int32"."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    name = dotted_name(node)
    return name.rsplit(".", 1)[-1] if name else None


def _operand_dtype(arg: ast.AST) -> Optional[str]:
    """Dtype of a pallas_call operand when statically evident: the idiomatic
    ``x.astype(jnp.int32)`` cast at the call site."""
    if isinstance(arg, ast.Call) and call_name(arg) == "astype" and arg.args:
        return _dtype_name(arg.args[0])
    return None


def _out_struct_dtypes(node: Optional[ast.AST], tuples: dict) -> list[Optional[str]]:
    out: list[Optional[str]] = []
    if node is None:
        return out
    for el in _as_sequence(node, tuples):
        if isinstance(el, ast.Call) and call_name(el) == "ShapeDtypeStruct":
            dt = el.args[1] if len(el.args) > 1 else _kw(el, "dtype")
            out.append(_dtype_name(dt))
    return out


def _grid_rank(node: Optional[ast.AST], tuples: dict, resolve) -> Optional[int]:
    if node is None:
        return None
    if isinstance(node, ast.Name) and node.id in tuples:
        node = tuples[node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        return len(node.elts)
    return 1 if resolve(node) is not None else None


@register(RULE)
def check(module: LintModule, config: LintConfig) -> Iterable[Finding]:
    if not module.relpath.startswith(config.kernel_prefix):
        return
    parents = parent_map(module.tree)
    menv = _module_env(module.tree)

    # map pallas_call -> enclosing function (for env) and -> outer Call (for
    # the operand list: pl.pallas_call(...)(query, data))
    for fn in [n for n in ast.walk(module.tree)
               if isinstance(n, ast.FunctionDef)]:
        env, tuples = _fn_env(fn, menv)
        resolve = const_resolver(env)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and call_name(node) == "pallas_call"):
                continue
            where = dict(path=module.relpath, line=node.lineno,
                         col=node.col_offset)

            grid = _grid_rank(_kw(node, "grid"), tuples, resolve)
            if grid is None:
                yield Finding(rule=RULE, message=(
                    "cannot determine grid rank statically; write grid as a "
                    "literal tuple (or a local tuple assignment)"), **where)

            in_specs = _blockspecs(_kw(node, "in_specs"), tuples)
            out_specs = _blockspecs(_kw(node, "out_specs"), tuples)
            out_dtypes = _out_struct_dtypes(_kw(node, "out_shape"), tuples)

            # operands: the immediately-enclosing call applies the kernel
            outer = parents.get(node)
            operands: list[ast.AST] = []
            if isinstance(outer, ast.Call) and outer.func is node:
                operands = list(outer.args)
                if in_specs and len(operands) != len(in_specs):
                    yield Finding(rule=RULE, message=(
                        f"{len(in_specs)} in_specs but {len(operands)} "
                        f"operands applied to the pallas_call"), **where)

            total_bytes = 0
            assumed = False
            for i, spec in enumerate(in_specs + out_specs):
                # index-map arity vs grid rank
                imap = spec.args[1] if len(spec.args) > 1 \
                    else _kw(spec, "index_map")
                if isinstance(imap, ast.Lambda) and grid is not None:
                    arity = len(imap.args.args)
                    if arity != grid:
                        yield Finding(
                            rule=RULE, path=module.relpath,
                            line=spec.lineno, col=spec.col_offset,
                            message=(f"BlockSpec index_map takes {arity} "
                                     f"indices but the grid has rank {grid}"))
                # tile footprint
                shape = spec.args[0] if spec.args else None
                dims: list[int] = []
                if isinstance(shape, (ast.Tuple, ast.List)):
                    unresolved = False
                    for el in shape.elts:
                        if isinstance(el, ast.Constant) and el.value is None:
                            continue  # squeezed dim: size 1
                        v = resolve(el)
                        if v is None:
                            unresolved = True
                        else:
                            dims.append(v)
                    if unresolved:
                        dims.append(config.assume_dim)
                        assumed = True
                n_in = len(in_specs)
                if i < n_in:
                    dt = _operand_dtype(operands[i]) if i < len(operands) \
                        else None
                else:
                    j = i - n_in
                    dt = out_dtypes[j] if j < len(out_dtypes) else None
                nbytes = _DTYPE_BYTES.get(dt, _FALLBACK_BYTES)
                tile = nbytes
                for d in dims:
                    tile *= d
                total_bytes += tile

            if total_bytes > config.vmem_budget_bytes:
                note = " (unresolved dims assumed " \
                       f"{config.assume_dim})" if assumed else ""
                yield Finding(rule=RULE, message=(
                    f"estimated VMEM tile footprint {total_bytes} bytes "
                    f"exceeds the {config.vmem_budget_bytes}-byte budget"
                    f"{note}; shrink the block shapes or raise "
                    f"--vmem-budget-mb with a rationale"), **where)

            # count-dtype policy on every kernel output
            for dt in out_dtypes:
                if dt is not None and dt not in config.kernel_out_dtypes:
                    yield Finding(rule=RULE, message=(
                        f"out_shape dtype {dt} violates the registry count "
                        f"policy {sorted(config.kernel_out_dtypes)}: kernels "
                        f"emit exact int32 counts; narrowing happens after "
                        f"the kernel via as_count_dtype (a float round-trip "
                        f"caps exactness at 2^24)"), **where)
