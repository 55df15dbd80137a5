"""Generic (non-JAX) rules: FTP005, FTP007, FTP009, FTP101, FTP102.

FTP005 absorbs the bare-print lint that used to live inline in
``tests/test_telemetry.py``: telemetry output must flow through
``TelemetryLogger`` / ``Tracer`` so that parity and event streams stay
byte-stable, so ``print`` is only allowed in the two modules that *are*
the output layer.  Test worker scripts that speak a stdout protocol to a
parent process suppress per-line with a justification.
"""

from __future__ import annotations

import ast
from typing import Iterable

from fedtpu.analysis.engine import Finding, rule

# Modules whose whole point is writing to stdout.  Matched by path suffix so
# both "fedtpu/cli.py" and "/abs/path/fedtpu/cli.py" hit.
PRINT_ALLOWLIST: tuple[str, ...] = (
    "fedtpu/telemetry/log.py",
    "fedtpu/cli.py",
    "fedtpu/resilience/supervisor.py",
    "fedtpu/resilience/chaos.py",
)

# Modules allowed to terminate the process: the CLI surface and the
# supervisor layer, whose exit codes ARE the restart contract
# (docs/resilience.md). Library code must raise instead — a sys.exit
# deep in the round loop would silently skip the checkpoint drain,
# tracer flush, and the supervisor's rc dispatch.
EXIT_ALLOWLIST: tuple[str, ...] = (
    "fedtpu/cli.py",
    "fedtpu/resilience/supervisor.py",
    "fedtpu/resilience/chaos.py",
    # The collective watchdog's os._exit(75): a stuck collective cannot be
    # unwound with an exception (the thread is blocked in native code), so
    # the only sound move is the process-level preemption exit.
    "fedtpu/resilience/distributed.py",
)


def _suffix_match(path: str, allowlist: tuple[str, ...]) -> bool:
    norm = path.replace("\\", "/")
    return any(norm.endswith(suffix) for suffix in allowlist)


def _path_allowlisted(path: str) -> bool:
    return _suffix_match(path, PRINT_ALLOWLIST)


@rule(
    "FTP005",
    "bare-print",
    "print() outside the telemetry output layer; route through "
    "TelemetryLogger/Tracer so logs stay parseable and parity-stable.",
)
def check_bare_print(tree: ast.AST, src: str, path: str) -> Iterable[Finding]:
    if _path_allowlisted(path):
        return
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            yield Finding(
                rule="FTP005",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message="bare print(); use the telemetry logger "
                "(fedtpu/telemetry/log.py) or a Tracer event",
            )


@rule(
    "FTP007",
    "library-exit",
    "sys.exit()/os._exit() outside the CLI/supervisor layer; library "
    "code must raise so checkpoint drain, tracer flush, and the "
    "supervisor's exit-code contract stay intact.",
)
def check_library_exit(tree: ast.AST, src: str, path: str) -> Iterable[Finding]:
    if _suffix_match(path, EXIT_ALLOWLIST):
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = None
        if isinstance(f, ast.Name) and f.id == "exit":
            name = "exit"
        elif (isinstance(f, ast.Attribute)
              and isinstance(f.value, ast.Name)):
            if f.value.id == "sys" and f.attr == "exit":
                name = "sys.exit"
            elif f.value.id == "os" and f.attr in ("_exit", "abort"):
                name = f"os.{f.attr}"
        if name:
            yield Finding(
                rule="FTP007",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message=f"{name}() in library code bypasses checkpoint "
                "drain and the supervisor exit-code contract "
                "(docs/resilience.md); raise an exception instead",
            )


@rule(
    "FTP009",
    "socket-no-timeout",
    "socket.socket() / create_connection() without an explicit timeout: "
    "a blocking socket with no deadline hangs the caller forever when "
    "the peer wedges (the failure mode the serving retry ladder and "
    "wire-fault drills exist to survive).",
)
def check_socket_timeout(tree: ast.AST, src: str,
                         path: str) -> Iterable[Finding]:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        is_ctor = (isinstance(f, ast.Attribute)
                   and isinstance(f.value, ast.Name)
                   and f.value.id == "socket" and f.attr == "socket")
        is_connect = (
            (isinstance(f, ast.Name) and f.id == "create_connection")
            or (isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id == "socket"
                and f.attr == "create_connection"))
        if is_ctor:
            # The constructor NEVER takes a timeout, so every call site
            # must either settimeout()/setblocking(False) and say so in
            # a noqa justification, or switch to create_connection.
            yield Finding(
                rule="FTP009",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message="socket.socket() starts blocking with no "
                "deadline; settimeout()/selectors it and justify with "
                "a noqa, or use socket.create_connection(..., timeout=)",
            )
        elif is_connect and not any(k.arg == "timeout"
                                    for k in node.keywords):
            yield Finding(
                rule="FTP009",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message="create_connection() without timeout= blocks "
                "forever on a wedged peer; pass an explicit timeout",
            )


@rule(
    "FTP101",
    "mutable-default-arg",
    "Mutable default argument ([]/{} / set()) shared across calls.",
)
def check_mutable_default(tree: ast.AST, src: str, path: str) -> Iterable[Finding]:
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]
        for d in defaults:
            bad = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call)
                and isinstance(d.func, ast.Name)
                and d.func.id in {"list", "dict", "set"}
                and not d.args
                and not d.keywords
            )
            if bad:
                yield Finding(
                    rule="FTP101",
                    path=path,
                    line=d.lineno,
                    col=d.col_offset,
                    message="mutable default argument is shared across calls; "
                    "default to None and construct inside the body",
                )


def _is_pass_only(body: list[ast.stmt]) -> bool:
    return all(isinstance(s, ast.Pass) for s in body) or (
        len(body) == 1
        and isinstance(body[0], ast.Expr)
        and isinstance(body[0].value, ast.Constant)
        and body[0].value.value is Ellipsis
    )


@rule(
    "FTP102",
    "except-swallow",
    "Bare `except:` or `except Exception:` whose body only passes — "
    "silently eats errors including tracer leaks and XLA failures.",
)
def check_except_swallow(tree: ast.AST, src: str, path: str) -> Iterable[Finding]:
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        broad = node.type is None or (
            isinstance(node.type, ast.Name)
            and node.type.id in {"Exception", "BaseException"}
        )
        if broad and _is_pass_only(node.body):
            yield Finding(
                rule="FTP102",
                path=path,
                line=node.lineno,
                col=node.col_offset,
                message="broad except swallows all errors; narrow the "
                "exception type, log it, or justify with a noqa",
            )
