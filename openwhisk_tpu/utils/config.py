"""Typed configuration loading.

Rebuild of the reference's two config systems (SURVEY §5.6):
  - WhiskConfig env-var map (common/scala/.../core/WhiskConfig.scala) —
    required properties validated at boot;
  - pureconfig case-class loading with `CONFIG_whisk_...` env overrides
    (docs/concurrency.md:28-40).

Here every component declares a frozen dataclass; `load_config` materializes
it from (defaults <- file dict <- env overrides). Env keys follow the
reference convention: CONFIG_whisk_loadBalancer_timeoutFactor=2 maps onto
key path ("load_balancer", "timeout_factor").
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, Optional, Type, TypeVar, get_args, get_origin

C = TypeVar("C")

_CAMEL = re.compile(r"(?<!^)(?=[A-Z])")


#: where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: is unset: ONE fixed path inside the checkout, derived from this file's
#: own location. The directory is part of JAX's cache key, so it must never
#: depend on the cwd, a pid, a port or a timestamp.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
JAX_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


class DeviceError(RuntimeError):
    """The device path was asked to run on a backend it must not use."""


def boot_jax() -> None:
    """Process-boot JAX setup; every entry point that uses JAX calls this
    before its first JAX op (it initializes no backend itself).

    The persistent compilation cache is on: the balancer compiles one
    program per power-of-two (R, H, B) bucket plus the shadow / scorer /
    telemetry programs, most of them sub-second, so the thresholds that
    would skip small or fast compiles are lowered to store everything.
    JAX_COMPILATION_CACHE_DIR, when set, is read by JAX itself and no
    directory is set in code; otherwise the cache is `JAX_CACHE_DIR`.

    The CPU twin (JAX_PLATFORMS names cpu first) is left alone: its
    compiles are quick, and on this stack XLA:CPU's loader logs a spurious
    E-level machine-feature mismatch for every cached executable it loads."""
    if cpu_requested(os.environ.get("JAX_PLATFORMS")):
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def cpu_requested(jax_platforms: Optional[str]) -> bool:
    """Does a JAX_PLATFORMS value make the CPU the default backend? The
    first platform it lists is the default one: `cpu` and `cpu,tpu` ask for
    the CPU, `tpu,cpu` (a TPU host's usual setting) does not."""
    return (jax_platforms or "").split(",")[0].strip().lower() == "cpu"


def check_device_platform(platform: str, jax_platforms: Optional[str]) -> None:
    """The device-path rule: run on a TPU, or on the CPU only when
    JAX_PLATFORMS names cpu first (tests, the CPU twin). JAX falls
    back to the CPU with a warning when it finds no accelerator; serving
    from that fallback would make a CPU run indistinguishable from a chip
    run, so it is an error here."""
    if platform == "tpu":
        return
    if platform == "cpu" and cpu_requested(jax_platforms):
        return
    raise DeviceError(
        f"the device balancer needs a TPU but JAX resolved platform "
        f"{platform!r} (JAX_PLATFORMS={jax_platforms!r}); export "
        f"JAX_PLATFORMS=cpu to run the CPU twin on purpose")


def device_info() -> Dict[str, Any]:
    """{platform, device_kind, device_count} as JAX reports them, after
    enforcing `check_device_platform`. Initializes the backend."""
    import jax

    devices = jax.devices()
    check_device_platform(devices[0].platform,
                          os.environ.get("JAX_PLATFORMS"))
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def _snake(name: str) -> str:
    return _CAMEL.sub("_", name).lower()


def config_from_env(prefix: str = "CONFIG_whisk_", environ: Optional[Dict[str, str]] = None
                    ) -> Dict[str, Any]:
    """Collect CONFIG_whisk_a_bC=v env vars into a nested {a: {b_c: v}} dict."""
    environ = environ if environ is not None else dict(os.environ)
    out: Dict[str, Any] = {}
    for k, v in environ.items():
        if not k.startswith(prefix):
            continue
        path = [_snake(p) for p in k[len(prefix):].split("_") if p]
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
            if not isinstance(node, dict):
                break
        else:
            node[path[-1]] = v
    return out


def _coerce(tp, value):
    origin = get_origin(tp)
    if origin is not None:
        args = [a for a in get_args(tp) if a is not type(None)]
        if origin is Optional or (origin is type(None)):
            return _coerce(args[0], value) if args else value
        if str(origin) in ("typing.Union", "types.UnionType") or origin.__name__ == "UnionType":
            return _coerce(args[0], value) if args else value
        if origin in (list, tuple):
            if isinstance(value, str):
                value = json.loads(value)
            inner = args[0] if args else str
            seq = [_coerce(inner, v) for v in value]
            return tuple(seq) if origin is tuple else seq
        if origin is dict:
            if isinstance(value, str):
                value = json.loads(value)
            return dict(value)
        return value
    if dataclasses.is_dataclass(tp) and isinstance(value, dict):
        return load_config(tp, value)
    if tp is dict:
        # bare `dict` fields (no typing origin): env values arrive as JSON
        # strings, e.g. CONFIG_whisk_slo_overrides='{"ns": {...}}'
        if isinstance(value, str):
            value = json.loads(value)
        return dict(value)
    if tp is bool:
        if isinstance(value, str):
            return value.strip().lower() in ("1", "true", "yes", "on")
        return bool(value)
    if tp in (int, float, str):
        return tp(value)
    return value


def load_config(cls: Type[C], data: Optional[Dict[str, Any]] = None,
                env_path: Optional[str] = None) -> C:
    """Build dataclass `cls` from defaults, overridden by `data`, overridden
    by CONFIG_whisk_<env_path>_* env vars (when env_path is given)."""
    data = dict(data or {})
    if env_path is not None:
        env = config_from_env()
        node: Any = env
        for p in env_path.split("."):
            if not isinstance(node, dict):
                node = None
                break
            node = node.get(p)
        if isinstance(node, dict):
            data = _deep_merge(data, node)
    kwargs = {}
    fields = {f.name: f for f in dataclasses.fields(cls)}
    for name, f in fields.items():
        if name in data:
            kwargs[name] = _coerce(f.type if not isinstance(f.type, str) else _resolve(cls, f), data[name])
    return cls(**kwargs)


def _resolve(cls, f):
    import typing
    hints = typing.get_type_hints(cls)
    return hints.get(f.name, str)


def _deep_merge(base: Dict, over: Dict) -> Dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


class RequiredPropertiesError(Exception):
    pass


def require_properties(props: Dict[str, Optional[str]]) -> Dict[str, str]:
    """WhiskConfig-style boot validation (ref WhiskConfig.scala): every key
    must have a non-None value or boot fails."""
    missing = [k for k, v in props.items() if v is None]
    if missing:
        raise RequiredPropertiesError(f"missing required properties: {', '.join(missing)}")
    return {k: v for k, v in props.items() if v is not None}
