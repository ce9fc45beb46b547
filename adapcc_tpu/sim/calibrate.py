"""Fit the α-β model from real traces and persist it as a JSON artifact.

Two calibration sources, in preference order:

1. **Probe CSVs** — the profiler's ``topo_profile_*`` shards
   (``src,dst,type,value`` rows): two points per directed link give exact
   per-link (α, β).
2. **Hardware-battery JSONL** — ``benchmarks/results/hw_<tag>.jsonl`` rows
   from :mod:`benchmarks.hw_session`: busbw sweep rows carry
   ``(collective, world, size_bytes, time_us)``, and each collective's
   round/byte structure (ring algebra: allreduce = 2(w−1) serial hops
   carrying ``2(w−1)/w`` of the payload per link, …) turns the sweep into a
   linear system in (α, β).

The fitted coefficients persist to a versioned JSON artifact so later
hardware-free sessions stay anchored to the last good hardware round:
having no chip changes *how* numbers are produced, not *what* they are
calibrated to.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from adapcc_tpu.sim.cost_model import (
    DCN,
    ICI,
    LinkCoeffs,
    LinkCostModel,
    fit_alpha_beta,
)

CALIBRATION_VERSION = 1

#: where the bootstrap persists the artifact, beside the other topology
#: artifacts (ip_table, strategy.xml — docs/OPERATIONS.md §2)
DEFAULT_CALIBRATION_PATH = os.path.join("topology", "calibration.json")

#: serial round count and per-link byte fraction for the ring realization of
#: each collective: time ≈ rounds(w)·α + byte_factor(w)·size·β.  The byte
#: factors match the nccl-tests busbw corrections (benchmarks/collectives.py
#: BUS_FACTORS); the round counts are the matching ring-schedule depths.
_RING_STRUCTURE = {
    "allreduce": (lambda w: 2 * (w - 1), lambda w: 2 * (w - 1) / w),
    "reduce_scatter": (lambda w: w - 1, lambda w: (w - 1) / w),
    "all_gather": (lambda w: w - 1, lambda w: (w - 1) / w),
    "all_to_all": (lambda w: w - 1, lambda w: (w - 1) / w),
    "broadcast": (lambda w: w - 1, lambda w: 1.0),
    "reduce": (lambda w: w - 1, lambda w: 1.0),
}


@dataclass
class Calibration:
    """Serializable α-β calibration: class coefficients + optional per-link
    table, stamped with provenance.

    Hygiene stamps (docs/ADAPT.md §3): ``fingerprint`` is the topology
    fingerprint the coefficients were fitted on (a calibration from one
    fabric must not silently price another — :func:`load_or_default` warns
    loudly on a mismatch), ``samples`` counts the measurements behind the
    fit (the decay weight :func:`merge_calibration` blends by), and
    ``provenance`` chains the merge history so an artifact always says how
    it came to hold its numbers.  All three default empty, so pre-stamp
    artifacts load unchanged.
    """

    world: int
    classes: Dict[str, LinkCoeffs]
    links: Dict[Tuple[int, int], LinkCoeffs] = field(default_factory=dict)
    ips: Optional[Dict[int, str]] = None
    source: str = "unspecified"
    version: int = CALIBRATION_VERSION
    #: topology fingerprint (adapcc_tpu.tuner.db.topology_fingerprint) the
    #: fit was taken on; None = unstamped (legacy artifact)
    fingerprint: Optional[str] = None
    #: measurements behind the fit — the weight re-calibration merges by
    samples: int = 0
    #: bounded merge-history chain, newest last
    provenance: Optional[List[str]] = None

    # -- model -----------------------------------------------------------------

    def cost_model(self) -> LinkCostModel:
        return LinkCostModel(
            self.world,
            links=self.links,
            classes=self.classes,
            ips=self.ips,
            source=self.source,
        )

    # -- (de)serialization -----------------------------------------------------

    def to_dict(self) -> dict:
        return {
            "version": self.version,
            "source": self.source,
            "world": self.world,
            "classes": {
                cls: {"alpha": c.alpha, "beta": c.beta}
                for cls, c in self.classes.items()
            },
            "links": [
                {"src": s, "dst": d, "alpha": c.alpha, "beta": c.beta}
                for (s, d), c in sorted(self.links.items())
            ],
            "ips": {str(r): ip for r, ip in (self.ips or {}).items()} or None,
            "fingerprint": self.fingerprint,
            "samples": int(self.samples),
            "provenance": list(self.provenance) if self.provenance else None,
        }

    @classmethod
    def from_dict(cls, obj: Mapping) -> "Calibration":
        version = int(obj.get("version", 0))
        if version != CALIBRATION_VERSION:
            raise ValueError(
                f"calibration artifact version {version} != supported "
                f"{CALIBRATION_VERSION}; re-calibrate from traces"
            )
        classes = {
            name: LinkCoeffs(float(c["alpha"]), float(c["beta"]))
            for name, c in (obj.get("classes") or {}).items()
        }
        links = {
            (int(l["src"]), int(l["dst"])): LinkCoeffs(
                float(l["alpha"]), float(l["beta"])
            )
            for l in (obj.get("links") or [])
        }
        ips_raw = obj.get("ips")
        ips = {int(r): ip for r, ip in ips_raw.items()} if ips_raw else None
        prov = obj.get("provenance")
        return cls(
            world=int(obj["world"]),
            classes=classes,
            links=links,
            ips=ips,
            source=str(obj.get("source", "unspecified")),
            version=version,
            fingerprint=(
                str(obj["fingerprint"]) if obj.get("fingerprint") else None
            ),
            samples=int(obj.get("samples") or 0),
            provenance=[str(p) for p in prov] if prov else None,
        )

    def save(self, path: str) -> str:
        """Atomic write (tmp + rename), the checkpoint.py artifact rule."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        os.rename(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "Calibration":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _from_model(model: LinkCostModel) -> Calibration:
    return Calibration(
        world=model.world,
        classes=dict(model.classes),
        links=dict(model.links),
        ips=model.ips,
        source=model.source,
    )


def calibrate_from_matrices(
    lat: np.ndarray,
    bw: np.ndarray,
    ips: Optional[Mapping[int, str]] = None,
    source: str = "matrices",
) -> Calibration:
    """Per-link fit from the profiler's latency [s] / bandwidth [GB/s]
    matrices (in-memory variant of the CSV path)."""
    return _from_model(LinkCostModel.from_matrices(lat, bw, ips, source=source))


def calibrate_from_profile_dir(
    topology_dir: str, world: int, ips: Optional[Mapping[int, str]] = None
) -> Calibration:
    """Per-link fit from on-disk ``topo_profile_*`` CSV shards."""
    return _from_model(
        LinkCostModel.from_topo_profile_dir(topology_dir, world, ips)
    )


def _battery_rows(jsonl_path: str) -> List[dict]:
    """Collective-sweep rows inside a battery artifact: rows lists from
    sweep phases, plus any single parsed row shaped like a BenchResult."""
    rows: List[dict] = []
    with open(jsonl_path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            candidates = list(rec.get("rows") or [])
            if not candidates and isinstance(rec.get("parsed"), dict):
                # "parsed" duplicates rows[-1] when a rows list exists
                # (hw_session._run keeps both) — counting it again would
                # double-weight the largest sweep size in the lstsq fit
                candidates.append(rec["parsed"])
            for row in candidates:
                if (
                    isinstance(row, dict)
                    and row.get("collective") in _RING_STRUCTURE
                    and row.get("time_us")
                    and row.get("size_bytes")
                    and int(row.get("world", 0)) >= 2
                ):
                    rows.append(row)
    return rows


def calibrate_from_battery(
    jsonl_path: str, impls: Tuple[str, ...] = ("xla", "pallas_ring")
) -> Optional[Calibration]:
    """Fit one (α, β) pair from a committed hardware-battery artifact.

    Only baseline impls are used by default — strategy-schedule rows measure
    the *schedule under test*, not the wire, and folding them in would
    calibrate the model to its own prediction target.  Returns ``None`` when
    the artifact holds no usable sweep rows (e.g. the busbw phase timed out),
    so callers fall through to the next calibration source.
    """
    rows = [r for r in _battery_rows(jsonl_path) if r.get("impl") in impls]
    if len(rows) < 2:
        return None
    a = []
    y = []
    for r in rows:
        w = int(r["world"])
        rounds_fn, byte_fn = _RING_STRUCTURE[r["collective"]]
        a.append([float(rounds_fn(w)), byte_fn(w) * float(r["size_bytes"])])
        y.append(float(r["time_us"]) * 1e-6)
    if np.linalg.matrix_rank(np.array(a)) < 2:
        # a rank-deficient design (e.g. every row proportional) cannot
        # separate α from β — lstsq would return a minimum-norm fantasy
        return None
    (alpha, beta), *_ = np.linalg.lstsq(np.array(a), np.array(y), rcond=None)
    coeffs = LinkCoeffs(alpha=max(0.0, float(alpha)), beta=max(0.0, float(beta)))
    world = max(int(r["world"]) for r in rows)
    return Calibration(
        world=world,
        classes={ICI: coeffs, DCN: LinkCoeffs(*_dcn_guess(coeffs))},
        source=f"battery:{os.path.basename(jsonl_path)}",
    )


def _dcn_guess(ici: LinkCoeffs) -> Tuple[float, float]:
    """A battery sweep on one slice says nothing about DCN; scale the ICI
    fit by the default class ratio so cross-host edges stay priced worse."""
    from adapcc_tpu.sim.cost_model import DEFAULT_COEFFS

    a_ratio = DEFAULT_COEFFS[DCN][0] / DEFAULT_COEFFS[ICI][0]
    b_ratio = DEFAULT_COEFFS[DCN][1] / DEFAULT_COEFFS[ICI][1]
    return ici.alpha * a_ratio, ici.beta * b_ratio


#: merge-history entries retained on a calibration artifact — enough to
#: audit a long re-calibration chain without growing the file unboundedly
MAX_PROVENANCE = 8


def merge_calibration(
    base: Calibration, update: Calibration, decay: float = 0.5
) -> Calibration:
    """Fold a re-calibration into an existing artifact WITH decay — the
    fix for last-writer-wins (docs/ADAPT.md §3).

    Coefficients blend per class (and per link) by sample-count weight:
    the update enters at its own ``samples``, the base is discounted by
    ``decay`` (an unstamped base borrows the update's weight, so a legacy
    artifact still decays instead of being overwritten).  Classes/links
    only one side knows survive unchanged — a correction that localized to
    one link class must not reset the others.  The merged artifact keeps
    the sample accounting and a bounded provenance chain, so the next
    merge decays THIS merge in turn.
    """
    if base.world != update.world:
        raise ValueError(
            f"cannot merge calibrations across worlds "
            f"({base.world} vs {update.world}); re-calibrate for this world"
        )
    if (
        base.fingerprint is not None
        and update.fingerprint is not None
        and base.fingerprint != update.fingerprint
    ):
        # blending two fabrics' fits and stamping the chimera with one
        # fingerprint would make every FUTURE load trust it silently —
        # the exact hygiene hole the stamps exist to close.  Callers with
        # a stale artifact start a fresh base instead.
        raise ValueError(
            f"cannot merge calibrations across fabrics (base fitted on "
            f"{base.fingerprint!r}, update on {update.fingerprint!r}); "
            "seed a fresh artifact for this fabric instead"
        )
    if not 0.0 <= decay <= 1.0:
        raise ValueError(f"decay must be in [0, 1], got {decay}")
    w_new = float(max(1, update.samples))
    w_old = decay * float(base.samples if base.samples > 0 else w_new)

    def blend(old: LinkCoeffs, new: LinkCoeffs) -> LinkCoeffs:
        if w_old + w_new <= 0:
            return new
        return LinkCoeffs(
            alpha=(w_old * old.alpha + w_new * new.alpha) / (w_old + w_new),
            beta=(w_old * old.beta + w_new * new.beta) / (w_old + w_new),
        )

    classes = dict(base.classes)
    for cls_name, c in update.classes.items():
        classes[cls_name] = (
            blend(base.classes[cls_name], c) if cls_name in base.classes else c
        )
    links = dict(base.links)
    for link, c in update.links.items():
        links[link] = blend(base.links[link], c) if link in base.links else c
    provenance = list(base.provenance or [])
    if not provenance and base.source:
        provenance.append(base.source)
    provenance.append(update.source)
    return Calibration(
        world=base.world,
        classes=classes,
        links=links,
        ips=update.ips if update.ips is not None else base.ips,
        source=f"merged:{update.source}",
        fingerprint=update.fingerprint or base.fingerprint,
        samples=int(round(w_old + w_new)),
        provenance=provenance[-MAX_PROVENANCE:],
    )


def load_calibration(path: str = DEFAULT_CALIBRATION_PATH) -> LinkCostModel:
    """Artifact → ready-to-use cost model (raises if absent/incompatible)."""
    return Calibration.load(path).cost_model()


def _stamp_warning(what: str) -> None:
    print(f"[sim] calibration WARNING: {what}", file=sys.stderr, flush=True)


def load_or_default(
    path: str = DEFAULT_CALIBRATION_PATH,
    world: Optional[int] = None,
    fingerprint: Optional[str] = None,
) -> LinkCostModel:
    """Artifact if present, else the synthetic defaults — the simulated
    bench's entry point, which must produce numbers either way.

    ``fingerprint`` (when given) is checked against the artifact's stamp:
    a calibration fitted on another fabric still *loads* — class-level
    coefficients transfer better than nothing — but the mismatch is
    reported LOUDLY, as is a world-size resize, so a stale artifact can
    never silently price a different pod (docs/ADAPT.md §3)."""
    try:
        cal = Calibration.load(path)
        # build the model INSIDE the fallback guard: an artifact that
        # parses but carries unusable values (world: 0, ...) must fall
        # back too — this entry point produces numbers either way
        model = cal.cost_model()
    except (OSError, ValueError, KeyError, TypeError) as e:
        # unreadable OR structurally malformed (hand-edited / partial tool /
        # version-gated) artifacts all fall back — this entry point must
        # produce numbers.  But an artifact that EXISTS and still failed is
        # a silently-discarded calibration: say so, or sim-rank quietly
        # commits to strategies priced on synthetic defaults
        if os.path.exists(path):
            print(
                f"[sim] calibration artifact {path} unusable "
                f"({type(e).__name__}: {e}); pricing with synthetic defaults",
                file=sys.stderr,
                flush=True,
            )
        return LinkCostModel.uniform(world or 8, source="defaults")
    if (
        fingerprint is not None
        and cal.fingerprint is not None
        and cal.fingerprint != fingerprint
    ):
        _stamp_warning(
            f"{path} was fitted on fabric {cal.fingerprint!r} but this "
            f"world's fingerprint is {fingerprint!r}; class coefficients "
            "still price the sweep, but re-calibrate before trusting a "
            "ranking on them"
        )
    if world is not None and world != model.world:
        _stamp_warning(
            f"{path} was fitted at world={model.world}, loading for "
            f"world={world}; per-link fits outside the new range fall back "
            "to class means"
        )
        # a calibration from another world still prices links by class —
        # keeping the recorded host layout when it covers the new rank
        # range, so cross-host edges stay classed DCN after the resize
        ips = None
        if model.ips and all(r in model.ips for r in range(world)):
            ips = {r: model.ips[r] for r in range(world)}
        return LinkCostModel(
            world,
            # in-range per-link fits survive the shrink; out-of-range links
            # (and a grown world's new links) fall back to class means
            links={
                (s, d): c
                for (s, d), c in model.links.items()
                if s < world and d < world
            },
            classes=model.classes,
            ips=ips,
            source=model.source + f"@world{world}",
        )
    return model
