"""ballista-lint: AST-based invariant checker for the Ballista-TPU tree.

The device path's correctness story rests on conventions the compiler
cannot see; this package turns them into machine-checked gates
(`python -m dev.analysis ballista_tpu/`):

- **readback-discipline** — every device->host materialization of a
  compiled-program result inside `ballista_tpu/ops/` or
  `ballista_tpu/parallel/` must pair with `record_readback` (or the
  `readback` helper) in the same function, or readback_stats' rows/bytes
  (the benchmark's `readback`) undercount and the O(limit)-readback claim is unmeasured.
- **tracer-hygiene** — code reached from a jit/shard_map/pallas decoration
  site must never branch (`if`/`while`) on, or host-materialize
  (`bool()`/`int()`/`float()`/`.item()`), a value derived from `jnp.*`/
  `jax.lax.*` calls: those are tracers during compilation.
- **dtype-discipline** — float64 must not reach traced code or flow into a
  device transfer (`jnp.asarray`/`jax.device_put`); the f64->f32 narrowing
  policy (ops/runtime.py module docstring) holds everywhere except
  ops/floatbits.py's deliberate order-preserving bijections. Host-side
  post-readback widening to f64 is the documented result dtype and is not
  flagged.
- **guarded-by** — state registered with a `# guarded-by: <lock>` comment
  may only be touched inside `with <lock>:` (or in a function annotated
  `# holds-lock: <lock>`, whose callers are checked instead). File-scoped
  by design: analysis is per-file so caching stays sound.
- **decline-discipline** — device paths bail to host only through the
  canonical signals: `raise UnsupportedOnDevice("<reason>")` (a reason is
  mandatory) or the `ops/kernels.py` helpers `decline`/`host_fallback`;
  an `except UnsupportedOnDevice` handler must not silently `return None`,
  and ad-hoc `Exception`/`RuntimeError`/`NotImplementedError` raises are
  not decline channels.
- **routing-discipline** / **failure-discipline** (`rules_routing.py`,
  `rules_failure.py`) — tier-routing and retry/requeue conventions; see
  their module docstrings.
- **lock-order** (`rules_lockorder.py` + `lockgraph.py` +
  `lockorder.toml`) — whole-program acquired-while-held graph, deadlock
  cycles, manifest-declared ordering, the check-then-act atomicity
  sub-check, and the `--check-witness` runtime cross-check (repeatable:
  per-process `<OUT>.<pid>` dumps from forked CI workers are merged).
- **durability** (`rules_durability.py` + `durability.toml`) — every
  attribute on SchedulerState/SchedulerServer/_PushSubscriber must carry
  `# durability: durable(<kv-prefix>) | derived(<rebuild-fn>) |
  ephemeral(<reason>)` agreeing with the reviewed manifest; durable
  mutations must pair with a same-scope KV op against the declared
  prefix, derived rebuilds must be reachable from `recover()`, ephemeral
  counts are budgeted per class, and `save_task_status` callers must
  consult the attempt/ledger guard (or carry `# attempt-guard-ok:`).

Suppression syntax (a reason is mandatory, checked by the always-on
`lint-usage` meta rule):

    something_flagged()  # ballista-lint: disable=<rule> -- <reason>

A standalone suppression comment covers the following line. Fixture files
under tests/ can opt into device-path scoping with a header comment
`# ballista-lint: path=ballista_tpu/ops/<virtual>.py`.

Zero third-party dependencies (stdlib ast/tokenize only); per-file result
caching keyed on (mtime, size, analyzer hash) in .ballista_lint_cache.json.
"""

from dev.analysis.core import RULE_NAMES, analyze_file, run_paths  # noqa: F401
