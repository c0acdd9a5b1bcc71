"""Ahead-of-time compile-cache population (`dprf prewarm`).

A worker joining a fleet should start hashing in seconds, not minutes:
every step shape a job will compile is deterministic, so a fleet image
can be baked with the persistent compilation cache already populated.
This module iterates (engine, attack, batch) specs -- seeded from the
tuning cache's entries and/or an explicit --engines/--attacks list --
builds each worker's step through the SAME factory path a job uses,
and compiles it ahead of time (``jax.jit(...).lower().compile()``)
without sweeping any keyspace.  A later job warmup of the same shape
then loads the cached executable instead of re-running XLA.

Mask shapes prewarm self-contained.  Wordlist shapes require the
job's REAL wordlist (and rule set): the compiled program embeds the
packed word table as constants, so content is part of the cache key.

Fan-out: ``jobs > 1`` shards the spec list over child processes (XLA
compiles hold the GIL-free C++ thread but each process compiles one
program at a time; independent specs parallelize across processes).
Each child is this same entrypoint with ``--spec-json``; results come
back as marker-prefixed JSON lines on stdout, so a partially-failed
child still reports every spec it finished.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import List, Optional, Sequence

#: stdout marker for child -> parent result lines
RESULT_MARKER = "PREWARM_JSON:"

#: fallback batch when a spec has no tuning-cache entry (matches the
#: CLI's pre-tuning default, cli.DEFAULT_BATCH)
DEFAULT_BATCH = 1 << 18


@dataclasses.dataclass
class PrewarmSpec:
    engine: str
    #: "mask" | "wordlist" | "combinator" | "hybrid-wm" | "hybrid-mw"
    attack: str = "mask"
    batch: int = DEFAULT_BATCH
    hit_cap: int = 64
    mask: str = "?a?a?a?a?a?a?a?a"
    rules: Optional[str] = None
    #: wordlist/hybrid attacks: the REAL wordlist file.  The compiled
    #: program embeds the packed word table as constants (verified:
    #: identical content hits, different content misses), so a
    #: synthetic stand-in would cache a program no job ever runs --
    #: "covered" in the report, cold on the fleet.
    wordlist: Optional[str] = None
    #: combinator attacks: the job's REAL "LEFT,RIGHT" word files
    #: (both tables are embedded, same contract as wordlist)
    combinator: Optional[str] = None
    #: >1 = the sharded (multi-chip mesh) step shape at this many
    #: devices; skipped gracefully when the host has fewer
    devices: int = 1

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "PrewarmSpec":
        return cls(**{k: v for k, v in d.items()
                      if k in {f.name for f in dataclasses.fields(cls)}})


class SkipSpec(Exception):
    """A spec this HOST cannot prewarm (e.g. a sharded shape on a
    single-device box) -- reported as skipped, never as an error."""


@dataclasses.dataclass
class PrewarmResult:
    engine: str
    attack: str
    batch: int
    compile_s: float = 0.0
    cache: str = "off"              # hit | miss | off | skip
    error: Optional[str] = None
    devices: int = 1
    skipped: Optional[str] = None   # why the host skipped the spec

    def as_dict(self) -> dict:
        d = {"engine": self.engine, "attack": self.attack,
             "batch": self.batch, "compile_s": round(self.compile_s, 3),
             "cache": self.cache, "devices": self.devices}
        if self.error:
            d["error"] = self.error
        if self.skipped:
            d["skipped"] = self.skipped
        return d


def tune_seeded_specs(device: str = "jax", hit_cap: int = 64,
                      mask: str = "?a?a?a?a?a?a?a?a",
                      rules: Optional[str] = None,
                      wordlist: Optional[str] = None,
                      devices: int = 1,
                      log=None) -> List[PrewarmSpec]:
    """Specs for every tuning-cache entry recorded for this device:
    `dprf tune` has already decided the batch each engine runs at, so
    those are exactly the shapes a fleet will compile.

    Wordlist entries need the job's ACTUAL wordlist (and rule set):
    the compiled program embeds the packed word table and the rule
    operations, so prewarming a wordlist shape with stand-ins would
    cache a program no real job runs -- reported as covered while the
    fleet still cold-compiles.  Without --wordlist those entries are
    skipped loudly, never faked."""
    from dprf_tpu.tune import default_cache, env_fingerprint
    cache = default_cache()
    specs: List[PrewarmSpec] = []
    for key, entry in sorted(cache.entries().items()):
        parts = dict(p.split("=", 1) for p in key.split("|") if "=" in p)
        if parts.get("device") != device:
            continue
        engine = parts.get("engine")
        attack = parts.get("attack", "mask")
        if not engine or attack not in ("mask", "wordlist"):
            continue
        if attack == "wordlist" and not wordlist:
            if log is not None:
                log.warn("skipping wordlist tune entry: prewarming "
                         "its program needs the job's real wordlist "
                         "(--wordlist, and --rules if the job uses "
                         "one)", key=key)
            continue
        # env-validated exactly like a job's lookup: a stale entry
        # (jax upgrade, engine edit, other chip) would prewarm a batch
        # no `--batch auto` job will resolve to -- reported covered
        # while the fleet still cold-compiles
        entry = cache.get(key, env_fingerprint(engine, device))
        if entry is None:
            if log is not None:
                log.warn("skipping stale tune entry (environment "
                         "fingerprint mismatch); re-run `dprf tune`",
                         key=key)
            continue
        try:
            batch = int(entry.get("batch", 0))
        except (TypeError, ValueError):
            continue
        if batch <= 0:
            continue
        try:
            cap = int(parts.get("hit_cap", hit_cap))
        except ValueError:
            cap = hit_cap
        specs.append(PrewarmSpec(
            engine=engine, attack=attack, batch=batch, hit_cap=cap,
            mask=mask,
            rules=rules if attack == "wordlist" else None,
            wordlist=wordlist if attack == "wordlist" else None,
            devices=max(1, int(devices))))
    return specs


def explicit_specs(engines: Sequence[str], attacks: Sequence[str],
                   hit_cap: int = 64, mask: str = "?a?a?a?a?a?a?a?a",
                   rules: Optional[str] = None,
                   wordlist: Optional[str] = None,
                   combinator: Optional[str] = None,
                   batch=None, devices: int = 1) -> List[PrewarmSpec]:
    """engines x attacks, batch resolved per engine from the tuning
    cache (``batch=None``/"auto") or pinned by an explicit int.  The
    tuned-batch lookup carries the same key extras a job's resolution
    uses (hit_cap, and rules_n for wordlist attacks with a rule set),
    so prewarm compiles the batch the job will actually run.
    ``devices > 1`` builds every spec's SHARDED (multi-chip mesh)
    shape instead of the single-device one."""
    from dprf_tpu.tune import lookup_tuned_batch
    rules_n = None
    if rules:
        from dprf_tpu.rules.parser import load_rules
        rules_n = len(load_rules(rules))
    specs = []
    for eng in engines:
        for attack in attacks:
            if batch in (None, "auto"):
                extras = {"hit_cap": hit_cap}
                if attack == "wordlist" and rules_n:
                    extras["rules_n"] = rules_n
                b = lookup_tuned_batch(eng, attack=attack, device="jax",
                                       extras=extras) or DEFAULT_BATCH
            else:
                b = int(batch)
            hybrid = attack in ("hybrid-wm", "hybrid-mw")
            specs.append(PrewarmSpec(
                engine=eng, attack=attack, batch=b, hit_cap=hit_cap,
                mask=mask,
                rules=rules if attack == "wordlist" else None,
                wordlist=(wordlist if attack == "wordlist" or hybrid
                          else None),
                combinator=(combinator if attack == "combinator"
                            else None),
                devices=max(1, int(devices))))
    return specs


def _combinator_gen(spec: PrewarmSpec, oracle):
    """Combinator/hybrid generator from the spec's REAL word files
    (both side tables are embedded in the compiled program, so
    stand-ins are refused exactly like wordlist shapes; the hybrid
    mask side is synthesized from spec.mask, as in a real job)."""
    from dprf_tpu.cli import _build_combinator_gen
    from dprf_tpu.utils.logging import DEFAULT as log
    if spec.attack == "combinator":
        if not spec.combinator:
            raise ValueError(
                "combinator prewarm needs the job's real left,right "
                "word files (--combinator LEFT,RIGHT): the compiled "
                "program embeds both word tables")
        arg = spec.combinator
    else:
        if not spec.wordlist:
            raise ValueError(
                f"{spec.attack} prewarm needs the job's real wordlist "
                "(--wordlist FILE): the compiled program embeds the "
                "word table, so a synthetic list would cache a "
                "program no job runs")
        arg = (f"{spec.wordlist},{spec.mask}"
               if spec.attack == "hybrid-wm"
               else f"{spec.mask},{spec.wordlist}")
    gen, _, _ = _build_combinator_gen(spec.attack, arg, {}, None,
                                      oracle, "jax", log)
    return gen


def _build_worker(spec: PrewarmSpec):
    """The job path's worker for this spec (engine factory selection
    included, so the prewarmed program is the one a real job runs)."""
    from dprf_tpu import get_engine
    oracle = get_engine(spec.engine, device="cpu")
    dev = get_engine(spec.engine, device="jax")
    # unmatchable single target (bench's trick: prewarm needs the step
    # shape, not cracks); engines whose targets need salts/params
    # raise here and are reported as skipped
    target = oracle.parse_target("ff" * oracle.digest_size)
    if spec.attack == "wordlist":
        if not spec.wordlist:
            raise ValueError(
                "wordlist-attack prewarm needs the job's real wordlist "
                "(--wordlist FILE): the compiled program embeds the "
                "packed word table, so a synthetic list would cache a "
                "program no job runs")
        from dprf_tpu.cli import _wordlist_max_len
        from dprf_tpu.generators.wordlist import WordlistRulesGenerator
        # same packing width as the job (coordinator-derived), so the
        # cached program is byte-identical to the one a worker warms
        gen = WordlistRulesGenerator.from_files(
            spec.wordlist, spec.rules,
            max_len=_wordlist_max_len(spec.engine, oracle, "jax"))
        maker_name = "make_wordlist_worker"
    elif spec.attack in ("combinator", "hybrid-wm", "hybrid-mw"):
        gen = _combinator_gen(spec, oracle)
        maker_name = "make_combinator_worker"
    else:
        from dprf_tpu.generators.mask import MaskGenerator
        gen = MaskGenerator(spec.mask)
        maker_name = "make_mask_worker"
    if spec.devices > 1:
        # sharded (multi-chip mesh) shape through the UNIFIED sharded
        # runtime (parallel/sharded.py) -- the same engine factory
        # path a `--devices N` job selects, so the cached programs
        # (per-batch step AND the capped superstep big units dispatch)
        # are exactly the ones a job warms
        import jax
        have = len(jax.devices())
        if have < spec.devices:
            raise SkipSpec(f"host has {have} device(s); the sharded "
                           f"shape needs {spec.devices}")
        from dprf_tpu.parallel.mesh import make_mesh
        smaker = getattr(
            dev, "make_sharded_" + maker_name[len("make_"):], None)
        if not callable(smaker):
            # a `--devices N` job for this engine warns and falls back
            # to one chip (cli._select_worker); mirror that as a skip,
            # not an error, so a fleet-wide sharded bake over mixed
            # engines doesn't read as failed
            raise SkipSpec(f"engine {spec.engine} has no sharded "
                           f"{spec.attack} worker (a job falls back "
                           "to one chip)")
        per_dev = (max(1, spec.batch // gen.n_rules)
                   if spec.attack == "wordlist" else spec.batch)
        return smaker(gen, [target], make_mesh(spec.devices), per_dev,
                      hit_capacity=spec.hit_cap, oracle=oracle)
    maker = getattr(dev, maker_name, None)
    if not callable(maker):
        raise ValueError(f"engine {spec.engine} has no {spec.attack} "
                         "device worker")
    return maker(gen, [target], batch=spec.batch,
                 hit_capacity=spec.hit_cap, oracle=oracle)


def prewarm_one(spec: PrewarmSpec, log=None) -> PrewarmResult:
    """Build + compile one spec's step; never raises (a fleet-image
    prewarm must report per-spec failures and keep going)."""
    try:
        worker = _build_worker(spec)
        if not getattr(worker, "_warmed", False):
            # AOT: populate the cache without dispatching
            worker.aot_compile()
        # (Pallas workers arrive warmed by their factory -- their
        # compile already went through the observer.)
        return PrewarmResult(
            spec.engine, spec.attack, spec.batch,
            compile_s=getattr(worker, "compile_seconds", 0.0),
            cache=getattr(worker, "compile_cache", "off"),
            devices=spec.devices)
    except SkipSpec as e:
        # not an error: this host simply cannot compile the shape
        # (e.g. a sharded spec on a single-device box); the fleet
        # image builder runs prewarm on a host that can
        if log is not None:
            log.info("prewarm spec skipped", engine=spec.engine,
                     attack=spec.attack, devices=spec.devices,
                     reason=str(e))
        return PrewarmResult(spec.engine, spec.attack, spec.batch,
                             cache="skip", devices=spec.devices,
                             skipped=str(e))
    except Exception as e:   # noqa: BLE001 -- parse/build/compile errors
        if log is not None:
            log.warn("prewarm spec failed", engine=spec.engine,
                     attack=spec.attack,
                     error=f"{type(e).__name__}: {e}")
        return PrewarmResult(spec.engine, spec.attack, spec.batch,
                             devices=spec.devices,
                             error=f"{type(e).__name__}: {e}")


def run_prewarm(specs: Sequence[PrewarmSpec], jobs: int = 1,
                log=None) -> List[PrewarmResult]:
    """Compile every spec; ``jobs > 1`` fans out over child processes
    (round-robin sharding keeps heavyweight engines spread out)."""
    specs = list(specs)
    if jobs <= 1 or len(specs) <= 1:
        return [prewarm_one(s, log=log) for s in specs]
    return _run_children(specs, jobs, log=log)


def _run_children(specs: List[PrewarmSpec], jobs: int,
                  log=None) -> List[PrewarmResult]:
    import subprocess

    shards = [specs[i::jobs] for i in range(min(jobs, len(specs)))]
    procs = []
    for shard in shards:
        cmd = [sys.executable, "-m", "dprf_tpu", "prewarm", "--jobs",
               "1", "-q", "--spec-json",
               json.dumps([s.as_dict() for s in shard])]
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    results: List[PrewarmResult] = []
    for shard, proc in zip(shards, procs):
        out, err = proc.communicate()
        got = []
        for line in out.splitlines():
            if line.startswith(RESULT_MARKER):
                try:
                    d = json.loads(line[len(RESULT_MARKER):])
                    got.append(PrewarmResult(
                        d["engine"], d["attack"], d["batch"],
                        compile_s=d.get("compile_s", 0.0),
                        cache=d.get("cache", "off"),
                        error=d.get("error"),
                        devices=d.get("devices", 1),
                        skipped=d.get("skipped")))
                except (ValueError, KeyError):
                    continue
        reported = {(r.engine, r.attack, r.batch, r.devices)
                    for r in got}
        for s in shard:                    # child died mid-shard
            if (s.engine, s.attack, s.batch, s.devices) not in reported:
                got.append(PrewarmResult(
                    s.engine, s.attack, s.batch, devices=s.devices,
                    error=f"prewarm child rc={proc.returncode}"))
        if proc.returncode != 0 and log is not None:
            log.warn("prewarm child failed", rc=proc.returncode,
                     stderr=err[-500:])
        results.extend(got)
    return results


def render_table(results: Sequence[PrewarmResult]) -> str:
    """The human summary `dprf prewarm` prints to stderr via the log
    (the stdout JSON line stays machine-parseable)."""
    rows = [("engine", "attack", "devs", "batch", "compile_s",
             "cached?")]
    for r in results:
        status = (r.error if r.error
                  else f"skipped ({r.skipped})" if r.skipped
                  else {"hit": "yes", "miss": "no (now cached)"}.get(
                      r.cache, r.cache))
        rows.append((r.engine, r.attack, str(r.devices), str(r.batch),
                     f"{r.compile_s:.2f}", status))
    widths = [max(len(row[i]) for row in rows)
              for i in range(len(rows[0]))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     for row in rows)
