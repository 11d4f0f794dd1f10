"""Tuned-profile store — versioned JSON, keyed by config signature.

A profile is the persisted winner of one ``cli.py tune`` search: the
knob assignment for one ``(engine, spec + constants, invariant set,
backend)`` configuration, written to ``PTT_TUNE_DIR`` (default
``~/.ptt_profiles``) as ``<sig>.json``.  Engines, bench.py, and the
daemon's CheckerPool look profiles up at construction;
``run_header.profile_sig`` then attributes every run (and every ledger
record) to the profile that shaped it.

Robustness contract (pinned in tests/test_tune.py): a corrupt,
stale-versioned, wrong-engine, or sig-mismatched profile file is
WARNED about and IGNORED — the engine falls back to its defaults,
never crashes, and a profile written for one config signature is
never applied to another (the embedded ``sig`` must match the lookup
key, so renaming a file cannot smuggle knobs across configs).

Profile file schema (validated by ``scripts/check_telemetry_schema.py
--profile``)::

    {
      "profile_v": 1,              # format version (mismatch = ignore)
      "sig": "<sha1 hex>",         # the config-signature key
      "engine": "device_bfs",      # target engine
      "backend": "cpu",            # jax backend it was tuned on
      "spec": "bookkeeper",        # human label only
      "created_unix": 1754300000.0,
      "knobs": {"fuse_group": 4, "fpset_dense_rounds": 2, ...},
      "tuner": {...}               # search provenance (free-form)
    }
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple, Union

from pulsar_tlaplus_tpu.tune import space as tune_space

PROFILE_VERSION = 1
TUNE_DIR_ENV = "PTT_TUNE_DIR"

# knob values must be JSON scalars (or the stages list-of-pairs) — the
# validator rejects anything an engine ctor would choke on
_SCALAR = (int, float, bool, str, type(None))

# range contracts per knob: the engines raise on these at
# construction, and the warn-and-ignore robustness contract says a
# bad profile must degrade to defaults, never crash — so the
# validator enforces the ranges BEFORE any knob reaches a ctor
_POSITIVE_INT_KNOBS = (
    "sub_batch", "flush_factor", "group", "fuse_group",
    "fpset_dense_rounds", "sweep_group", "miss_batch",
    # swarm-simulation knobs (r18, engine "sim")
    "n_walkers", "segment_len",
)


def profiles_dir() -> str:
    return os.environ.get(
        TUNE_DIR_ENV, os.path.expanduser("~/.ptt_profiles")
    )


def _warn(msg: str) -> None:
    print(f"note: tuned profile ignored: {msg}", file=sys.stderr)


# ------------------------------------------------------------ signature


def model_sig(model) -> str:
    """Model identity — the same contract as the engines' checkpoint
    ``_model_sig``: hand models carry their Constants in ``.c``;
    compiled specs are identified by module name + constant bindings +
    lane structure."""
    c = getattr(model, "c", None)
    if c is not None:
        return repr(c)
    spec = getattr(model, "spec", None)
    if spec is not None:
        return repr(
            (
                getattr(spec.module, "name", "?"),
                sorted(
                    (k, repr(v)) for k, v in spec.constants.items()
                ),
                tuple(getattr(model, "lane_labels", ())),
            )
        )
    return type(model).__name__


def profile_key(
    *,
    model,
    invariants: Tuple[str, ...],
    engine: str = "device_bfs",
    backend: Optional[str] = None,
    tiered: bool = False,
) -> str:
    """The profile's config-signature key: engine + model (spec +
    constant bindings) + invariant set + backend.  Capacity budgets
    (``max_states``) are deliberately excluded — they scale the run,
    not the schedule shape.  The tiered-store REGIME (r16) is folded
    in when active: a budgeted run's winning knobs are chosen under
    spill pressure and must never auto-resolve for the all-resident
    regime (or vice versa) — appended conditionally so every existing
    untiered key stands."""
    if backend is None:
        backend = default_backend()
    blob = repr(
        (engine, model_sig(model), tuple(invariants), backend)
        + (("tiered",) if tiered else ())
    )
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def default_backend() -> str:
    try:
        import jax

        b = jax.default_backend()
    except Exception:  # noqa: BLE001
        return "cpu"
    return "cpu" if b == "cpu" else "tpu"


# --------------------------------------------------------------- files


def path_for(sig: str) -> str:
    return os.path.join(profiles_dir(), f"{sig}.json")


def save(profile: dict) -> str:
    """Atomically write a profile to its keyed location; returns the
    path.  The caller builds the dict via :func:`build`."""
    errs = validate(profile)
    if errs:
        raise ValueError(
            "refusing to save an invalid profile: " + "; ".join(errs)
        )
    d = profiles_dir()
    os.makedirs(d, exist_ok=True)
    path = path_for(profile["sig"])
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(profile, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def build(
    *,
    sig: str,
    engine: str,
    backend: str,
    knobs: Dict,
    spec: str = "?",
    tuner: Optional[dict] = None,
) -> dict:
    return {
        "profile_v": PROFILE_VERSION,
        "sig": sig,
        "engine": engine,
        "backend": backend,
        "spec": spec,
        "created_unix": round(time.time(), 1),
        "knobs": dict(knobs),
        "tuner": dict(tuner or {}),
    }


def validate(profile, path: str = "<profile>") -> List[str]:
    """Structural violations in one profile dict (empty = valid)."""
    errs: List[str] = []
    if not isinstance(profile, dict):
        return [f"{path}: not a JSON object"]
    v = profile.get("profile_v")
    if v != PROFILE_VERSION:
        errs.append(
            f"{path}: profile_v {v!r} != supported {PROFILE_VERSION}"
        )
    for k in ("sig", "engine", "backend"):
        if not isinstance(profile.get(k), str) or not profile.get(k):
            errs.append(f"{path}: missing/empty {k!r}")
    knobs = profile.get("knobs")
    if not isinstance(knobs, dict):
        errs.append(f"{path}: knobs is not an object")
        return errs
    known = tune_space.PROFILE_KNOBS.get(
        str(profile.get("engine")), ()
    )
    for k, val in knobs.items():
        if known and k not in known:
            errs.append(
                f"{path}: unknown knob {k!r} for engine "
                f"{profile.get('engine')!r} (known: {sorted(known)})"
            )
        if k == "fpset_stages":
            ok = isinstance(val, (list, tuple)) and all(
                isinstance(s, (list, tuple))
                and len(s) == 2
                and all(isinstance(x, int) for x in s)
                and s[0] >= 2
                and s[1] >= 1
                for s in val
            )
            if not ok:
                errs.append(
                    f"{path}: fpset_stages must be [[div >= 2, "
                    "limit >= 1], ...]"
                )
        elif not isinstance(val, _SCALAR):
            errs.append(
                f"{path}: knob {k!r} has non-scalar value {val!r}"
            )
        elif k in _POSITIVE_INT_KNOBS and (
            isinstance(val, bool)
            or not isinstance(val, int)
            or val < 1
        ):
            # engines raise on these ranges at construction; a bad
            # profile must warn-and-ignore instead (module docstring)
            errs.append(
                f"{path}: knob {k!r} must be a positive integer "
                f"(got {val!r})"
            )
        elif k == "adapt" and not isinstance(val, bool):
            errs.append(
                f"{path}: knob adapt must be a boolean (got {val!r})"
            )
        elif k == "spill_compress" and not isinstance(val, bool):
            errs.append(
                f"{path}: knob spill_compress must be a boolean "
                f"(got {val!r})"
            )
        elif k == "hbm_headroom" and (
            isinstance(val, bool)
            or not isinstance(val, (int, float))
            or not (0.0 <= float(val) < 1.0)
        ):
            errs.append(
                f"{path}: knob hbm_headroom must be a fraction in "
                f"[0, 1) (got {val!r})"
            )
    return errs


def validate_file(path: str) -> List[str]:
    """``check_telemetry_schema.py --profile`` entry point: structural
    validation of one profile file, plus the filename/sig agreement
    the loader enforces."""
    try:
        with open(path) as f:
            profile = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    errs = validate(profile, path=path)
    base = os.path.splitext(os.path.basename(path))[0]
    sig = profile.get("sig") if isinstance(profile, dict) else None
    if isinstance(sig, str) and base != sig:
        errs.append(
            f"{path}: filename key {base!r} != embedded sig {sig!r} "
            "(the loader would ignore this file)"
        )
    return errs


def load(sig: str, engine: Optional[str] = None) -> Optional[dict]:
    """The profile stored under ``sig``, or None — warning (never
    raising) on a missing-but-corrupt, version-mismatched,
    wrong-engine, or sig-mismatched file."""
    path = path_for(sig)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            profile = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        _warn(f"{path} is unreadable ({e}); using defaults")
        return None
    errs = validate(profile, path=path)
    if errs:
        _warn(errs[0] + "; using defaults")
        return None
    if profile["sig"] != sig:
        # a profile written for one config-sig must NEVER be applied
        # to another — renamed/copied files fail here
        _warn(
            f"{path} embeds sig {profile['sig']!r} but was looked up "
            f"as {sig!r}; using defaults"
        )
        return None
    if engine is not None and profile["engine"] != engine:
        _warn(
            f"{path} targets engine {profile['engine']!r}, not "
            f"{engine!r}; using defaults"
        )
        return None
    return profile


def resolve(
    profile: Union[None, str, dict],
    *,
    model,
    invariants: Tuple[str, ...],
    engine: str = "device_bfs",
    tiered: bool = False,
) -> Optional[dict]:
    """Engine-side resolution: ``None`` -> no profile; ``"auto"`` ->
    look up by config signature; a dict -> validate + sig/engine
    check against THIS config (a caller-passed profile for a
    different config is ignored with a warning, same contract as the
    file loader); a path string -> load that file, same checks."""
    if profile is None:
        return None
    key = profile_key(
        model=model, invariants=invariants, engine=engine,
        tiered=tiered,
    )
    if isinstance(profile, dict):
        errs = validate(profile)
        if errs:
            _warn(errs[0] + "; using defaults")
            return None
        if profile["sig"] != key or profile["engine"] != engine:
            _warn(
                f"profile sig/engine ({profile.get('sig')!r}, "
                f"{profile.get('engine')!r}) do not match this config "
                f"({key!r}, {engine!r}); using defaults"
            )
            return None
        return profile
    if profile == "auto":
        return load(key, engine=engine)
    # an explicit path: load + hold to the same sig contract
    try:
        with open(profile) as f:
            prof = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        _warn(f"{profile} is unreadable ({e}); using defaults")
        return None
    return resolve(
        prof, model=model, invariants=invariants, engine=engine,
        tiered=tiered,
    )


def knobs_for(profile: Optional[dict], engine: str) -> Dict:
    """The profile's knob dict filtered to the engine's known knobs
    (``fpset_stages`` lists normalize to tuples)."""
    if not profile:
        return {}
    known = tune_space.PROFILE_KNOBS.get(engine, ())
    out: Dict = {}
    for k, v in (profile.get("knobs") or {}).items():
        if k not in known or v is None:
            continue
        if k == "fpset_stages":
            v = tuple(tuple(int(x) for x in s) for s in v)
        out[k] = v
    return out
