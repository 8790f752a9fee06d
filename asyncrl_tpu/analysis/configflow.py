"""Config-contract pass (CFG0xx).

The config layer (``utils/config.py``'s frozen ``Config`` dataclass +
``configs/presets.py``) is the ONE interface every subsystem reads its
knobs through — and the dataclass is the contract. This pass
cross-references every static read/write of that contract:

- CFG001 — a read of an undeclared field: ``config.<name>`` /
  ``cfg.<name>`` / ``self.config.<name>`` / ``getattr(config, "<name>")``
  where ``<name>`` is neither a dataclass field nor a method/property of
  the analyzed ``Config`` class; and a ``Config(...)``/
  ``config.replace(...)`` keyword that names no declared field. (The
  runtime raises for these too — but only on the code path that executes;
  a preset typo in a rarely-used branch ships silently without this.)
- CFG002 — a declared field no analyzed code reads (constructor keywords
  are writes, not reads). Dead config is a contract nobody honors: the
  field either gets a reader, gets deleted, or carries a documented
  ``# lint: config-unused-ok(<reason>)`` waiver at its declaration.
- CFG003 — an ``ASYNCRL_*`` environment variable access
  (``os.environ[...]``/``os.environ.get``/``os.getenv``, constants
  resolved through module names like ``faults.ENV_VAR``) that names a
  variable outside the sanctioned registry below: an unregistered env
  knob bypasses the config layer (no preset, no override parsing, no
  checkpoint compat record) and a TYPO'd one silently reads empty.

Receivers are recognized by name (``config``/``cfg`` parameters and
locals, ``self.config``/``self._config``/``self.cfg`` attributes) and by
type (``self.<attr> = Config(...)`` bindings) — the package-wide idiom.
Dynamic access (``getattr(config, key)`` with a runtime key, the override
parser) is out of static reach and deliberately skipped.
"""

from __future__ import annotations

import ast

from asyncrl_tpu.analysis.core import Finding, Project, SourceModule

# Every ASYNCRL_* env var the framework sanctions. An access to anything
# else ASYNCRL_-prefixed is CFG003 — add the variable here (with its
# owning module) when a new knob is deliberately introduced.
KNOWN_ENV_VARS = {
    "ASYNCRL_FAULTS",         # utils/faults.py — fault-injection grammar
    "ASYNCRL_DEBUG_SYNC",     # utils/debug.py — runtime invariant checks
    "ASYNCRL_FORCE_CPU",      # utils/runtime.py require_tpu — explicit CPU opt-in
    "ASYNCRL_SMOKE_UPDATES",  # scripts/*_smoke.sh harness sizing
    "ASYNCRL_CHAOS_STEPS",    # scripts/chaos_smoke.sh harness sizing
    "ASYNCRL_TRACE",          # obs/trace.py — arm pipeline tracing
    "ASYNCRL_TRACE_RING",     # obs/trace.py — per-thread ring capacity
    "ASYNCRL_REQUEST_TRACE",  # obs/requests.py — request hop journaling
    "ASYNCRL_RUN_DIR",        # obs/__init__.py — observability output dir
    "ASYNCRL_REPLAY",         # api/sebulba_trainer.py — replay-ring depth
    "ASYNCRL_SERVE",          # api/sebulba_trainer.py — serve-core toggle
    "ASYNCRL_SERVE_P95_MS",   # scripts/serve_smoke.sh p95 latency gate
    "ASYNCRL_OBS_PORT",       # obs/http.py — exposition endpoint port
    "ASYNCRL_OBS_HOST",       # obs/http.py — exposition bind host
    "ASYNCRL_GATEWAY_HOST",   # serve/gateway.py — gateway bind host
    "ASYNCRL_GATEWAY_QPS",    # scripts/gateway_smoke.sh load-gen rate
    "ASYNCRL_GATEWAY_P99_MS",  # scripts/gateway_smoke.sh p99 latency gate
    "ASYNCRL_INTROSPECT",     # obs/introspect.py — training introspection
    "ASYNCRL_ELASTIC",        # api/sebulba_trainer.py — elastic-runtime toggle
    "ASYNCRL_RESUME",         # runtime/durability.py — crash-consistent resume
    "ASYNCRL_DRAIN_GRACE_S",  # runtime/durability.py — preemption drain budget
}

_CONFIG_NAMES = {"config", "cfg"}
_CONFIG_ATTRS = {"config", "_config", "cfg"}


class _ConfigContract:
    """The analyzed ``Config`` dataclass: fields (AnnAssign declarations,
    with lines for CFG002) and readable non-field attributes (methods,
    properties)."""

    def __init__(self, module: SourceModule, node: ast.ClassDef):
        self.module = module
        self.node = node
        self.fields: dict[str, int] = {}
        self.methods: set[str] = set()
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name
            ):
                self.fields[stmt.target.id] = stmt.lineno
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.methods.add(stmt.name)

    @property
    def readable(self) -> set[str]:
        return set(self.fields) | self.methods


def _find_contract(project: Project) -> _ConfigContract | None:
    for module in project.modules:
        for node in module.tree.body:
            if not isinstance(node, ast.ClassDef) or node.name != "Config":
                continue
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                resolved = module.resolve(target)
                if resolved and resolved.rsplit(".", 1)[-1] == "dataclass":
                    return _ConfigContract(module, node)
    return None


def _config_typed_attrs(project: Project) -> set[tuple[str, str]]:
    """(ClassName, attr) pairs bound to Config by ``self.attr =
    Config(...)`` — plus the name-based ``self.config`` family."""
    typed: set[tuple[str, str]] = set()
    for info in project.class_list:
        for attr, type_name in info.attr_types.items():
            if type_name == "Config":
                typed.add((info.name, attr))
    return typed


def _module_config_names(module: SourceModule) -> set[str]:
    """Module-level names bound to Config values: ``x = Config(...)`` and
    the replace chains presets build (``atari = pong.replace(...)``),
    tracked in declaration order."""
    names = getattr(module, "_config_names", None)
    if names is not None:
        return names
    names = set()
    for stmt in module.tree.body:
        if not (
            isinstance(stmt, ast.Assign)
            and isinstance(stmt.value, ast.Call)
        ):
            continue
        func = stmt.value.func
        resolved = module.resolve(func)
        from_ctor = (
            resolved is not None
            and resolved.rsplit(".", 1)[-1] == "Config"
        )
        from_replace = (
            isinstance(func, ast.Attribute)
            and func.attr == "replace"
            and isinstance(func.value, ast.Name)
            and (func.value.id in names or func.value.id in _CONFIG_NAMES)
        )
        if from_ctor or from_replace:
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
    module._config_names = names
    return names


def _is_config_receiver(
    module: SourceModule,
    node: ast.AST,
    cls_name: str | None,
    typed: set[tuple[str, str]],
) -> bool:
    if isinstance(node, ast.Name):
        return (
            node.id in _CONFIG_NAMES
            or node.id in _module_config_names(module)
        )
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        if node.attr in _CONFIG_ATTRS:
            return True
        return cls_name is not None and (cls_name, node.attr) in typed
    return False


def _class_of_map(module: SourceModule) -> dict[int, str]:
    out: dict[int, str] = {}
    for cls in module.tree.body:
        if isinstance(cls, ast.ClassDef):
            for sub in ast.walk(cls):
                out[id(sub)] = cls.name
    return out


def _env_key(module: SourceModule, expr: ast.AST) -> str | None:
    """The env-var name of a key expression: a string constant or a Name/
    Attribute resolving to a module-level string constant (ENV_VAR)."""
    if isinstance(expr, ast.Constant):
        return expr.value if isinstance(expr.value, str) else None
    if isinstance(expr, (ast.Name, ast.Attribute)):
        from asyncrl_tpu.analysis.core import module_constant

        resolved = module.resolve(expr)
        if resolved is None:
            return None
        const = module_constant(module, resolved)
        if isinstance(const, ast.Constant) and isinstance(const.value, str):
            return const.value
    return None


def run(
    project: Project, targets: set[str] | None = None
) -> list[Finding]:
    """``targets`` (incremental cache): scopes CFG001/CFG003, which are
    per-file; CFG002 (never-read fields) folds reads from the whole
    project and is always recomputed (a global code for the cache)."""
    findings: list[Finding] = []
    contract = _find_contract(project)
    typed = _config_typed_attrs(project) if contract else set()
    reads: set[str] = set()

    for module in project.modules:
        module._project = project  # for ENV_VAR constant resolution
        in_target = targets is None or module.path in targets
        class_of = _class_of_map(module)
        for node in ast.walk(module.tree):
            if contract is not None and isinstance(node, ast.Attribute):
                if isinstance(node.ctx, ast.Load) and _is_config_receiver(
                    module, node.value, class_of.get(id(node.value)), typed
                ):
                    attr = node.attr
                    if attr.startswith("__"):
                        continue
                    reads.add(attr)
                    if attr not in contract.readable and in_target:
                        findings.append(
                            Finding(
                                "CFG001", module.path, node.lineno,
                                f"read of undeclared config field "
                                f"{attr!r}: not a field or method of the "
                                "Config dataclass "
                                f"({contract.module.path})",
                            )
                        )
            elif isinstance(node, ast.Call):
                _check_call(
                    project, module, node, contract, typed, class_of,
                    reads, findings if in_target else [],
                )
            elif isinstance(node, ast.Subscript):
                # os.environ["ASYNCRL_X"] — subscript form of the same
                # env-var discipline.
                if module.resolve(node.value) == "os.environ":
                    _check_env_key(
                        module, node.slice, node.lineno,
                        findings if in_target else [],
                    )

    if contract is not None:
        ann = contract.module.annotations
        # CFG002 is a GLOBAL code (cache.GLOBAL_CODES): it folds reads
        # from the whole project, so it must be emitted on every run
        # regardless of ``targets`` — gating it on the contract module
        # being a target would let a partial cached run drop it (and the
        # warm path would then replay the hidden result forever).
        for field, line in sorted(contract.fields.items()):
            if field in reads:
                continue
            if ann.waived(line, "config-unused-ok"):
                continue
            findings.append(
                Finding(
                    "CFG002", contract.module.path, line,
                    f"config field {field!r} is declared but never "
                    "read by any analyzed code: delete it, wire a "
                    "reader, or waive with "
                    "'# lint: config-unused-ok(<reason>)'",
                )
            )
    return findings


def _check_call(
    project: Project,
    module: SourceModule,
    node: ast.Call,
    contract: _ConfigContract | None,
    typed: set[tuple[str, str]],
    class_of: dict[int, str],
    reads: set[str],
    findings: list[Finding],
) -> None:
    func = node.func
    resolved = module.resolve(func)

    # --- getattr(config, "field"[, default]) ------------------------
    if (
        contract is not None
        and isinstance(func, ast.Name)
        and func.id == "getattr"
        and len(node.args) >= 2
        and _is_config_receiver(
            module, node.args[0], class_of.get(id(node.args[0])), typed
        )
    ):
        key = node.args[1]
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            reads.add(key.value)
            if key.value not in contract.readable:
                findings.append(
                    Finding(
                        "CFG001", module.path, node.lineno,
                        f"getattr read of undeclared config field "
                        f"{key.value!r}",
                    )
                )
        return

    # --- Config(...) / <config>.replace(...) keyword contracts ------
    if contract is not None:
        is_ctor = (
            resolved is not None
            and resolved.rsplit(".", 1)[-1] == "Config"
        )
        is_replace = (
            isinstance(func, ast.Attribute)
            and func.attr == "replace"
            and _is_config_receiver(
                module, func.value, class_of.get(id(func.value)), typed
            )
        )
        if is_ctor or is_replace:
            for kw in node.keywords:
                if kw.arg is None:  # **overrides: dynamic, skip
                    continue
                if kw.arg not in contract.fields:
                    what = "Config()" if is_ctor else ".replace()"
                    findings.append(
                        Finding(
                            "CFG001", module.path, node.lineno,
                            f"{what} keyword {kw.arg!r} names no declared "
                            "config field",
                        )
                    )

    # --- ASYNCRL_* env-var discipline -------------------------------
    if resolved in ("os.environ.get", "os.getenv") and node.args:
        _check_env_key(module, node.args[0], node.lineno, findings)


def _check_env_key(
    module: SourceModule,
    key_expr: ast.AST,
    line: int,
    findings: list[Finding],
) -> None:
    key = _env_key(module, key_expr)
    if key is None or not key.startswith("ASYNCRL_"):
        return
    if key not in KNOWN_ENV_VARS:
        findings.append(
            Finding(
                "CFG003", module.path, line,
                f"unregistered ASYNCRL_* env var {key!r}: not in the "
                "sanctioned registry (analysis/configflow.KNOWN_ENV_VARS) "
                "— a typo reads empty silently, and an unregistered knob "
                "bypasses the config layer",
            )
        )
