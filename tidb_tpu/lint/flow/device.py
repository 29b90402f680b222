"""Device-plane dataflow analysis over the lint forest.

Tier-1 runs on `JAX_PLATFORMS=cpu`, where the two nastiest device-plane
bug classes are structurally invisible: use-after-donate (silent
corruption on TPU, a harmless no-op on CPU) and retrace/recompile
hazards (visible only as the compile stalls the kernel profiler
measures after the fact, on silicon). This pass proves their absence
statically, BEFORE dispatch:

* **discovery** — every traced-program construction site in the
  package: `jax.jit(f, ...)`, `functools.partial(jax.jit, ...)` used
  as a decorator, and `devplane.plane_jit(...)` (unwrapping the
  `shard_map(fn, ...)` plumbing to the real traced callable), plus the
  kernel classes that own them and where each program is stored
  (self attribute, module global, bounded bucket dict, factory return);
* **donation analysis** — for every dispatch through a
  `donate_argnums` program: the donated operand must be a locally
  owned name with no live use after the dispatch on any path (reads
  through aliases, closure captures, and enclosing retry loops that
  would re-dispatch the freed buffer all count), and a donated
  `device_put_chunk` transfer must explicitly opt out of the chunk
  memo (a memoized donated buffer is a read-after-free);
* **cache-key analysis** — every `self` attribute / config read /
  module global reachable from a traced kernel body must be an
  operand or provably folded into the owning cache key
  (`FingerprintCache.get_or_create`, the executor/mesh dict cache,
  and the profiler-registration fingerprint), with
  `devplane.mesh_fingerprint` present in every key (PR 18's
  plane-identity contract);
* **retrace analysis** — dispatch operands must flow through the pow2
  superchunk bucketing (or a bounded bucket-map program memo, the
  `meshjoin._stage2_jits[bucket]` shape), static arguments must be
  hashable, and `float()`/`bool()`/`int()`/`.item()`/`np.asarray`
  coercions inside traced bodies are findings;
* **compile prediction** — a static per-kernel-family compile-count
  model (every construction site sits behind a cache/memo, so warm
  runs compile nothing) that tests/test_device_flow.py cross-checks
  against the profiler registry's observed counters — static
  analysis the profiler plane can falsify, and vice versa.

Zero extra parses: the pass walks the shared forest and reuses the
PR 7 call graph (`flow_of(forest).graph`); `device_flow_of(forest)` is
memoized on the forest like `flow_of` itself.  The three rules
consuming this live in tidb_tpu/lint/rules/device.py.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from tidb_tpu.lint.flow import flow_of

__all__ = ["DeviceFlow", "device_flow_of", "TracedSite", "DispatchSite"]

# helpers whose presence sanctions a dispatch's operand shaping: they
# are the pow2 superchunk bucketing seams (ops/runtime.py) and the
# per-kernel shard/pad entry points built on them
SHAPERS = frozenset({
    "bucket_size", "pad_column", "put_lanes", "device_put_chunk",
    "prepare_build",
    "_shard_probe", "_put_side", "superchunk_batches", "_bucket",
})

# callables whose results are trace-time Python values: calling them on
# traced values inside a kernel body forces a device sync / retrace
COERCIONS = frozenset({"float", "int", "bool"})
HOST_ARRAY_FNS = frozenset({("np", "asarray"), ("np", "array"),
                            ("numpy", "asarray"), ("numpy", "array"),
                            ("jax", "device_get")})

_MESH_ROOT = "<mesh>"          # pseudo-root: value derives from the
#                                device plane (covered by the mesh
#                                fingerprint in the cache key)


def _root_names(expr) -> set:
    """Bare Name roots of an expression (the base of attribute /
    subscript chains; call args recursed)."""
    out: set = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Name):
            out.add(node.id)
    return out


def _is_const(name: str) -> bool:
    return name.isupper() or name.lstrip("_").isupper()


def _call_name(call: ast.Call) -> str | None:
    """Trailing name of the callee: `runtime.bucket_size` ->
    'bucket_size', `self._bucket` -> '_bucket'."""
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def _is_jax_jit(expr) -> bool:
    return (isinstance(expr, ast.Attribute) and expr.attr == "jit"
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "jax")


def _is_plane_jit(expr) -> bool:
    if isinstance(expr, ast.Name):
        return expr.id == "plane_jit"
    return isinstance(expr, ast.Attribute) and expr.attr == "plane_jit"


def _is_mesh_fp(call: ast.Call) -> bool:
    return _call_name(call) in ("mesh_fingerprint", "mesh_generation")


def _int_tuple(expr) -> tuple:
    """Literal donate_argnums/static_argnums value -> tuple of ints."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int):
        return (expr.value,)
    if isinstance(expr, (ast.Tuple, ast.List)):
        return tuple(e.value for e in expr.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, int))
    return ()


def _str_tuple(expr) -> tuple:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return (expr.value,)
    if isinstance(expr, (ast.Tuple, ast.List)):
        return tuple(e.value for e in expr.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


@dataclass
class TracedSite:
    """One traced-program construction site."""
    rel: str
    line: int
    form: str                     # "jit" | "partial_jit" | "plane_jit"
    call: ast.Call | None         # the construction call (None for
    #                               decorator form)
    fns: list = field(default_factory=list)   # resolved traced
    #                               callables (FuncInfo), possibly
    #                               several (self._kernel fan-out)
    fn_name: str = ""             # display name of the traced callable
    owner: object = None          # FuncInfo of the enclosing function
    cls: str | None = None        # class owning the stored program
    store: tuple = ("anon", None)  # ("attr"|"global"|"dict"|"local"
    #                                |"decorator"|"return", name)
    donate: tuple = ()            # donated positions
    static_names: tuple = ()
    static_nums: tuple = ()

    @property
    def donating(self) -> bool:
        return bool(self.donate)


@dataclass
class DispatchSite:
    """One call of a traced program."""
    rel: str
    line: int
    call: ast.Call
    site: TracedSite              # the program being dispatched
    func: object = None           # enclosing FuncInfo
    via_factory: ast.Call | None = None   # inner factory/getter call
    #                               whose args key a program memo


class DeviceFlow:
    """The device-plane facts for one forest (see module docstring)."""

    def __init__(self, forest):
        self.forest = forest
        self.graph = flow_of(forest).graph
        self.sites: list[TracedSite] = []
        # program stores, for dispatch resolution
        self._attr_sites: dict[tuple, TracedSite] = {}   # (rel, attr)
        self._name_sites: dict[tuple, TracedSite] = {}   # (rel, name)
        self._factory_sites: dict[tuple, TracedSite] = {}  # FuncInfo.key
        self._node_func: dict[int, object] = {}          # id(def node)
        for fi in self.graph.funcs.values():
            self._node_func[id(fi.node)] = fi
        self._parents: dict[str, dict[int, ast.AST]] = {}
        self._discover()
        self.dispatches: list[DispatchSite] = self._find_dispatches()
        self._reachable_memo: dict[tuple, set] = {}

    # -- plumbing ------------------------------------------------------------

    def _parent_map(self, rel: str) -> dict[int, ast.AST]:
        pm = self._parents.get(rel)
        if pm is None:
            pf = self.forest.get(rel)
            pm = {}
            for node in pf.nodes:
                for child in ast.iter_child_nodes(node):
                    pm[id(child)] = node
            self._parents[rel] = pm
        return pm

    def enclosing_function(self, rel: str, node) -> object:
        """Innermost FuncInfo containing `node` (by parent walk)."""
        pm = self._parent_map(rel)
        cur = node
        while cur is not None:
            fi = self._node_func.get(id(cur))
            if fi is not None:
                return fi
            cur = pm.get(id(cur))
        return None

    def enclosing_class(self, rel: str, node) -> str | None:
        pm = self._parent_map(rel)
        cur = node
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur.name
            cur = pm.get(id(cur))
        return None

    def _resolve_callable(self, expr, rel: str, enclosing) -> list:
        """Resolve the traced-callable expression of a jit construction
        to FuncInfo(s). `self.X` that misses in the enclosing class
        fans out to every same-module method named X (base-class
        plumbing like MeshKernelBase._setup_mesh wraps the subclass's
        `_kernel`)."""

        class _Fake:
            func = expr
        hit = self.graph.resolve_call(_Fake, rel, enclosing)
        if hit is not None:
            return [hit]
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self":
            return [fi for (r, c, n), fi in self.graph._method.items()
                    if r == rel and n == expr.attr]
        return []

    def _unwrap_traced(self, expr, rel: str, owner) -> tuple:
        """-> (fns, display_name) for the first argument of a jit
        construction, unwrapping `shard_map(fn, ...)` wrappers, local
        names bound to them, and closure factories that `return` a
        nested def (the `_stage2_fn(bucket)` shape)."""
        if isinstance(expr, ast.Call):
            name = _call_name(expr)
            if name in ("shard_map", "named") and expr.args:
                # shard_map(fn, ...) / devplane.named(fn, family): the
                # traced callable is the wrapped one
                return self._unwrap_traced(expr.args[0], rel, owner)
            hits = self._resolve_callable(expr.func, rel, owner)
            # a factory that returns one of its nested defs: trace the
            # nested def
            out = []
            for fi in hits:
                ret = [n for n in ast.walk(fi.node)
                       if isinstance(n, ast.Return)]
                for r in ret:
                    if isinstance(r.value, ast.Name) and \
                            r.value.id in fi.nested:
                        out.append(fi.nested[r.value.id])
            if out:
                return out, out[0].node.name
            return [], ast.unparse(expr)[:40]
        if isinstance(expr, ast.Name) and owner is not None:
            # local bound to a shard_map(...) / traced fn
            for node in ast.walk(owner.node):
                if isinstance(node, ast.Assign) and \
                        any(isinstance(t, ast.Name) and t.id == expr.id
                            for t in node.targets):
                    if isinstance(node.value, ast.Call):
                        return self._unwrap_traced(node.value, rel,
                                                   owner)
        fns = self._resolve_callable(expr, rel, owner)
        name = expr.attr if isinstance(expr, ast.Attribute) else \
            (expr.id if isinstance(expr, ast.Name) else
             ast.unparse(expr)[:40])
        return fns, name

    # -- discovery -----------------------------------------------------------

    def _discover(self) -> None:
        for pf in self.forest:
            for node in pf.nodes:
                if isinstance(node, ast.Call):
                    if _is_jax_jit(node.func):
                        self._add_site(pf, node, "jit")
                    elif _is_plane_jit(node.func):
                        self._add_site(pf, node, "plane_jit")
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        if isinstance(dec, ast.Call) and \
                                _call_name(dec) == "partial" and \
                                dec.args and _is_jax_jit(dec.args[0]):
                            self._add_decorator_site(pf, node, dec)

    def _add_decorator_site(self, pf, fn_node, dec: ast.Call) -> None:
        fi = self._node_func.get(id(fn_node))
        site = TracedSite(pf.rel, dec.lineno, "partial_jit", dec,
                          fns=[fi] if fi else [],
                          fn_name=fn_node.name, owner=None,
                          cls=self.enclosing_class(pf.rel, fn_node),
                          store=("decorator", fn_node.name))
        for kw in dec.keywords:
            if kw.arg == "donate_argnums":
                site.donate = _int_tuple(kw.value)
            elif kw.arg == "static_argnums":
                site.static_nums = _int_tuple(kw.value)
            elif kw.arg == "static_argnames":
                site.static_names = _str_tuple(kw.value)
        self.sites.append(site)
        self._name_sites[(pf.rel, fn_node.name)] = site

    def _add_site(self, pf, call: ast.Call, form: str) -> None:
        owner = self.enclosing_function(pf.rel, call)
        cls = self.enclosing_class(pf.rel, call)
        site = TracedSite(pf.rel, call.lineno, form, call, owner=owner,
                          cls=cls)
        if call.args:
            site.fns, site.fn_name = self._unwrap_traced(
                call.args[0], pf.rel, owner)
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                site.donate = _int_tuple(kw.value)
            elif kw.arg == "static_argnums":
                site.static_nums = _int_tuple(kw.value)
            elif kw.arg == "static_argnames":
                site.static_names = _str_tuple(kw.value)
        site.store = self._store_of(pf.rel, call, owner)
        self.sites.append(site)
        kind, name = site.store
        if kind == "attr":
            self._attr_sites[(pf.rel, name)] = site
        elif kind in ("global", "local"):
            self._name_sites[(pf.rel, name)] = site
        if owner is not None and kind in ("dict", "return", "local"):
            # the enclosing function acts as a program factory/getter
            self._factory_sites[owner.key] = site

    def _store_of(self, rel: str, call: ast.Call, owner) -> tuple:
        """Where the constructed program lands: walk up to the
        statement and classify its target."""
        pm = self._parent_map(rel)
        cur: ast.AST = call
        stmt = None
        while cur is not None:
            if isinstance(cur, (ast.Assign, ast.AnnAssign, ast.Return)):
                stmt = cur
                break
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef, ast.Module)):
                break
            cur = pm.get(id(cur))
        if isinstance(stmt, ast.Return):
            return ("return", None)
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, ast.AnnAssign) and stmt.target is not None:
            targets = [stmt.target]
        # prefer attr/dict stores over tuple-assign locals
        for t in targets:
            if isinstance(t, ast.Subscript):
                base = t.value
                name = base.attr if isinstance(base, ast.Attribute) \
                    else (base.id if isinstance(base, ast.Name)
                          else None)
                return ("dict", name)
        for t in targets:
            if isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and \
                    t.value.id == "self":
                return ("attr", t.attr)
        for t in targets:
            if isinstance(t, ast.Name):
                kind = "global" if owner is None else "local"
                return (kind, t.id)
        return ("anon", None)

    # -- dispatch resolution -------------------------------------------------

    def _find_dispatches(self) -> list[DispatchSite]:
        out: list[DispatchSite] = []
        for pf in self.forest:
            for node in pf.nodes:
                if not isinstance(node, ast.Call):
                    continue
                d = self._classify_dispatch(pf.rel, node)
                if d is not None:
                    out.append(d)
        return out

    def _classify_dispatch(self, rel: str,
                           call: ast.Call) -> DispatchSite | None:
        fn = call.func
        fi = None
        # self._jit(...) / self._jitd(...) — attr stores, matched by
        # attribute name within the module (base-class dispatch methods
        # run with subclass instances)
        if isinstance(fn, ast.Attribute) and \
                isinstance(fn.value, ast.Name) and fn.value.id == "self":
            site = self._attr_sites.get((rel, fn.attr))
            if site is not None:
                fi = self.enclosing_function(rel, call)
                return DispatchSite(rel, call.lineno, call, site, fi)
            return None
        # _jit_sort(...) — module/local name stores
        if isinstance(fn, ast.Name):
            site = self._name_sites.get((rel, fn.id))
            if site is not None and site.call is not call:
                fi = self.enclosing_function(rel, call)
                # the local name may be bound to a factory result:
                # find its binding call for bucket-key checking
                via = None
                if fi is not None:
                    via = self._binding_factory_call(fi, fn.id)
                return DispatchSite(rel, call.lineno, call, site, fi,
                                    via_factory=via)
            # local name assigned from a factory call
            fi = self.enclosing_function(rel, call)
            if fi is not None:
                bound = self._binding_factory_call(fi, fn.id)
                if bound is not None:
                    hits = self._resolve_callable(bound.func, rel, fi)
                    for h in hits:
                        site = self._factory_sites.get(h.key)
                        if site is not None:
                            return DispatchSite(rel, call.lineno, call,
                                                site, fi,
                                                via_factory=bound)
            return None
        # _matcher_program(cap)(args) / self._get_stage2(bkt)(args)
        if isinstance(fn, ast.Call):
            fi = self.enclosing_function(rel, call)
            hits = self._resolve_callable(fn.func, rel, fi)
            for h in hits:
                site = self._factory_sites.get(h.key)
                if site is not None:
                    return DispatchSite(rel, call.lineno, call, site,
                                        fi, via_factory=fn)
        return None

    def _binding_factory_call(self, fi, name: str) -> ast.Call | None:
        """The call expression a local `name` is bound from in `fi`
        (prog = self._program(*key) / _PROGRAMS.get(cap) / ...)."""
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    any(isinstance(t, ast.Name) and t.id == name
                        for t in node.targets):
                return node.value
        return None

    # -- reachability --------------------------------------------------------

    def reachable(self, fi) -> list:
        """FuncInfos reachable from `fi` through the call graph
        (bounded BFS; the traced closure is small)."""
        memo = self._reachable_memo.get(fi.key)
        if memo is not None:
            return memo
        seen = {fi.key}
        out = [fi]
        queue = [fi]
        while queue and len(out) < 120:
            cur = queue.pop()
            for node in ast.walk(cur.node):
                if not isinstance(node, ast.Call):
                    continue
                hit = self.graph.resolve_call(node, cur.rel, cur)
                if hit is not None and hit.key not in seen:
                    seen.add(hit.key)
                    out.append(hit)
                    queue.append(hit)
        self._reachable_memo[fi.key] = out
        return out

    def traced_bodies(self, site: TracedSite) -> list:
        seen: set = set()
        out: list = []
        for fn in site.fns:
            for body in self.reachable(fn):
                if body.key not in seen:
                    seen.add(body.key)
                    out.append(body)
        return out

    # -- compile prediction --------------------------------------------------

    def compile_predictions(self) -> dict:
        """Static per-family compile model (tests/test_device_flow.py
        holds a warm run to it):
        every construction site sits behind a fingerprint cache or a
        bounded program memo, so (a) warm re-runs compile nothing and
        (b) fingerprint-cached families construct at most once per
        profile row. The profiler plane falsifies this if a seam
        regresses (and the lint rules falsify the profiler if a cache
        stops keying what the kernel reads)."""
        families: list[str] = []
        for pf in self.forest:
            if not pf.rel.endswith("profiler.py"):
                continue
            for node in pf.tree.body:
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "FAMILIES"
                        for t in node.targets) and \
                        isinstance(node.value, ast.Tuple):
                    families = [e.value for e in node.value.elts
                                if isinstance(e, ast.Constant)]
        # modules mentioning the family string own its construction
        # sites ("hashagg"/"scalaragg" are picked via a variable, so
        # the literal — not the profile() call arg — is the anchor)
        fam_rels: dict[str, set] = {f: set() for f in families}
        for pf in self.forest:
            for node in pf.nodes:
                if isinstance(node, ast.Constant) and \
                        node.value in fam_rels:
                    fam_rels[node.value].add(pf.rel)
        preds: dict[str, dict] = {}
        for fam in families:
            if fam == "plane":
                # plane rows key on the wrapped fn name; bucketed
                # program memos construct one unit per pow2 bucket and
                # kernel instance, so only warm stability is predicted
                preds[fam] = {"sites": sum(
                    1 for s in self.sites if s.form == "plane_jit"),
                    "per_row_bound": None, "warm_growth": 0}
            else:
                n_sites = sum(1 for s in self.sites
                              if s.rel in fam_rels[fam])
                preds[fam] = {"sites": n_sites, "per_row_bound": 1,
                              "warm_growth": 0}
        return preds


def device_flow_of(forest) -> DeviceFlow:
    """The forest's device-plane analysis, computed once and memoized
    on the forest instance (all three device rules and the compile
    predictions share the same facts)."""
    df = getattr(forest, "_device_flow", None)
    if df is None:
        df = DeviceFlow(forest)
        forest._device_flow = df
    return df
