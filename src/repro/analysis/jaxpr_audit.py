"""Layer 2 of repro-lint: jaxpr-level audits of the stack's contracts.

The AST layer (`repro.analysis.astlint`) checks what the SOURCE says; this
module checks what the TRACED PROGRAM does. It builds tiny canonical
instances of the stack's entry points — train forward/backward, chunked
prefill, the device-resident decode chunk, and both sequence-parallel
attention forms — traces them with `jax.make_jaxpr`, and walks the
resulting equations to enforce three invariants:

* **JX001 — host-effect-free decode body.** `model.decode_scan`'s scanned
  step is the serving hot loop; its one host sync happens at the CHUNK
  boundary (`np.asarray` in the engine), never inside the scan. Any
  callback / debug / infeed primitive inside a scanned body (or anywhere
  in the train/prefill traces) is a regression.

* **JX002 — collective bytes match the comm-cost model.** The
  sequence-parallel bodies in `core/seq_parallel.py` advertise their
  communication through `blockwise_sp_comm_bytes` and
  `seq_parallel_comm_bytes` (quoted in docs/parallelism.md and
  EXPERIMENTS.md). The audit traces the shard-local bodies under an
  `AbstractMesh`, measures the actual gathered / reduced operand bytes
  from the jaxpr's avals, and asserts equality with the model — the
  claimed O(k·d) cost is checked against the program, not prose.

* **JX003 — no dtype widening on the decode hot path.** No
  `convert_element_type` to float64/complex may appear in the decode
  trace (an accidental f64 constant would silently double cache
  bandwidth, or crash on accelerators without f64).

Tracing uses `jax.sharding.AbstractMesh`, so the audit runs on a
single-device host with no XLA device-count forcing. Findings reuse
:class:`repro.analysis.astlint.Finding` with paths like
``jaxpr:decode_scan`` and line 0 (there is no source line for a traced
equation). Expectation parameters are injectable so tests can prove each
audit actually fires (see tests/test_static_analysis.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Tuple

from repro.analysis.astlint import Finding

JX_RULES: Dict[str, str] = {
    "JX001": "host-effect primitive on a traced hot path",
    "JX002": "collective bytes diverge from the comm-cost model",
    "JX003": "dtype widening (f64/complex) on the decode hot path",
}

# primitive-name fragments that mean "this equation talks to the host"
HOST_EFFECT_FRAGMENTS = (
    "callback", "debug", "infeed", "outfeed", "host_",
)

WIDE_DTYPES = frozenset({"float64", "complex64", "complex128"})


@dataclasses.dataclass
class AuditResult:
    """Findings plus the measured-vs-model numbers behind them."""

    findings: List[Finding]
    stats: Dict[str, Dict[str, object]]

    @property
    def ok(self) -> bool:
        return not self.findings


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _as_jaxpr(obj):
    """Unwrap ClosedJaxpr -> Jaxpr; pass Jaxpr through; else None."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    return None


def _sub_jaxprs(eqn) -> Iterator[object]:
    """Yield every jaxpr nested in an equation's params (scan/cond/jit/
    shard_map bodies, custom-vjp branches, ...)."""
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else (val,)):
            sub = _as_jaxpr(item)
            if sub is not None:
                yield sub


def iter_eqns(jaxpr) -> Iterator[object]:
    """All equations of `jaxpr`, recursing into nested jaxprs."""
    jaxpr = _as_jaxpr(jaxpr)
    if jaxpr is None:
        return
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def aval_bytes(aval) -> int:
    size = 1
    for d in aval.shape:
        size *= int(d)
    return size * aval.dtype.itemsize


def collectives(jaxpr, names: Tuple[str, ...] = ("all_gather", "psum"),
                ) -> List[Dict[str, object]]:
    """Every collective equation with its OUTPUT aval byte volume (for an
    all-gather that is the gathered buffer; for a psum the reduced one —
    both are what the comm-cost model counts per device)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in names:
            out.append({
                "prim": name,
                "bytes": sum(aval_bytes(v.aval) for v in eqn.outvars),
                "shapes": [tuple(v.aval.shape) for v in eqn.outvars],
            })
    return out


def host_effect_prims(jaxpr) -> List[str]:
    found = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if any(frag in name for frag in HOST_EFFECT_FRAGMENTS):
            found.append(name)
    return found


def widenings(jaxpr, forbidden=WIDE_DTYPES) -> List[str]:
    """convert_element_type equations whose target dtype is forbidden."""
    found = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = str(eqn.params.get("new_dtype", ""))
        if new in forbidden:
            found.append(new)
    return found


def scan_bodies(jaxpr) -> List[object]:
    """Body jaxprs of every `scan` equation (recursively)."""
    bodies = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            sub = _as_jaxpr(eqn.params.get("jaxpr"))
            if sub is not None:
                bodies.append(sub)
    return bodies


def _finding(rule: str, entry: str, msg: str) -> Finding:
    return Finding(rule=rule, path=f"jaxpr:{entry}", line=0, msg=msg)


# ---------------------------------------------------------------------------
# Canonical tiny instances
# ---------------------------------------------------------------------------

# sequence-parallel audit dims: B=1 and float32 so the measured per-device
# aval bytes equal the comm model's (batch-free) count at dtype_bytes=4
_SP = dict(B=1, S=32, shards=2, H=4, Hkv=2, Dh=4, c=8, r=2)


def _tiny_cfg():
    from repro.configs.base import (AttentionConfig, LinformerConfig,
                                    ModelConfig)
    attn = AttentionConfig(
        kind="linformer_causal", backend="reference", num_heads=4,
        num_kv_heads=2, head_dim=8,
        linformer=LinformerConfig(block_size=8, block_slots=2))
    return ModelConfig(name="jaxpr-audit", num_layers=2, d_model=32,
                       vocab_size=256, max_seq_len=64, attention=attn,
                       dtype="float32", remat="none")


def _sp_inputs(rng_seed: int = 0):
    import jax
    import jax.numpy as jnp
    d = _SP
    ks = jax.random.split(jax.random.PRNGKey(rng_seed), 5)
    q = jax.random.normal(ks[0], (d["B"], d["S"], d["H"], d["Dh"]),
                          jnp.float32)
    k = jax.random.normal(ks[1], (d["B"], d["S"], d["Hkv"], d["Dh"]),
                          jnp.float32)
    v = jax.random.normal(ks[2], (d["B"], d["S"], d["Hkv"], d["Dh"]),
                          jnp.float32)
    return q, k, v, ks[3], ks[4]


# ---------------------------------------------------------------------------
# Audits
# ---------------------------------------------------------------------------


def audit_sp_causal(expect_lin: Optional[int] = None,
                    ) -> Tuple[List[Finding], Dict[str, object]]:
    """Trace the blockwise-causal sequence-parallel body and assert its
    all-gather volume equals `blockwise_sp_comm_bytes`.

    expect_lin overrides the model's expected byte count (tests inject a
    wrong value to prove the audit fires)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.core.seq_parallel import (blockwise_sp_comm_bytes,
                                         sp_blockwise_causal_attention)

    d = _SP
    q, k, v, ke, kf = _sp_inputs()
    E = jax.random.normal(ke, (d["c"], d["r"]), jnp.float32) * 0.3
    F = jax.random.normal(kf, (d["c"], d["r"]), jnp.float32) * 0.3
    mesh = AbstractMesh((d["shards"],), ("seq",))

    def body(q_l, k_l, v_l):
        return sp_blockwise_causal_attention(
            q_l, k_l, v_l, E, F, seq_axis="seq", block_size=d["c"],
            block_slots=d["r"], scale=d["Dh"] ** -0.5, fused=False)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False)
    jpr = jax.make_jaxpr(sharded)(q, k, v)

    gathers = [c for c in collectives(jpr) if c["prim"] == "all_gather"]
    measured = sum(c["bytes"] for c in gathers)
    d_total = d["Hkv"] * d["Dh"]
    model, _ = blockwise_sp_comm_bytes(
        d["S"], d["c"], d["r"], d_total, d["shards"], dtype_bytes=4)
    expected = model if expect_lin is None else expect_lin

    findings: List[Finding] = []
    if len(gathers) != 2:
        findings.append(_finding(
            "JX002", "sp_causal",
            f"expected exactly 2 all_gathers (compressed k/v prefix), "
            f"traced {len(gathers)}"))
    if measured != expected:
        findings.append(_finding(
            "JX002", "sp_causal",
            f"all-gather volume {measured}B != comm model "
            f"blockwise_sp_comm_bytes={expected}B"))
    stats = {"all_gathers": len(gathers), "gathered_bytes": measured,
             "model_bytes": model}
    return findings, stats


def audit_sp_exact(expect_lin: Optional[int] = None,
                   ) -> Tuple[List[Finding], Dict[str, object]]:
    """Trace the exact-form sequence-parallel body and assert its psum
    volume equals `seq_parallel_comm_bytes`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.core.seq_parallel import (seq_parallel_comm_bytes,
                                         sp_exact_linformer_attention)

    d = _SP
    K = (d["S"] // d["c"]) * d["r"]          # compressed width
    q, k, v, ke, kf = _sp_inputs()
    E = jax.random.normal(ke, (d["S"], K), jnp.float32) * 0.3
    F = jax.random.normal(kf, (d["S"], K), jnp.float32) * 0.3
    mesh = AbstractMesh((d["shards"],), ("seq",))

    def body(q_l, k_l, v_l, E_l, F_l):
        return sp_exact_linformer_attention(
            q_l, k_l, v_l, E_l, F_l, seq_axis="seq",
            scale=d["Dh"] ** -0.5, fused=False)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P("seq"), P("seq")),
        out_specs=P(None, "seq"), check_vma=False)
    jpr = jax.make_jaxpr(sharded)(q, k, v, E, F)

    psums = [c for c in collectives(jpr) if c["prim"] == "psum"]
    measured = sum(c["bytes"] for c in psums)
    d_total = d["Hkv"] * d["Dh"]
    model, _ = seq_parallel_comm_bytes(
        d["S"], K, d_total, d["shards"], dtype_bytes=4)
    expected = model if expect_lin is None else expect_lin

    findings: List[Finding] = []
    if len(psums) != 2:
        findings.append(_finding(
            "JX002", "sp_exact",
            f"expected exactly 2 psums (compressed k/v), traced "
            f"{len(psums)}"))
    if measured != expected:
        findings.append(_finding(
            "JX002", "sp_exact",
            f"psum volume {measured}B != comm model "
            f"seq_parallel_comm_bytes={expected}B"))
    stats = {"psums": len(psums), "psum_bytes": measured,
             "model_bytes": model}
    return findings, stats


def audit_decode(n_steps: int = 4, forbidden=WIDE_DTYPES,
                 ) -> Tuple[List[Finding], Dict[str, object]]:
    """Trace `model.decode_scan` (the serving decode chunk) and assert the
    scanned body is host-effect-free and nothing widens to f64/complex."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib

    cfg = _tiny_cfg()
    B = 2
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    cache = model_lib.init_cache(cfg, batch=B, max_seq=cfg.max_seq_len,
                                 dtype=jnp.float32)
    cur = jnp.zeros((B,), jnp.int32)
    fin = jnp.zeros((B,), bool)
    rng = jax.random.PRNGKey(1)

    jpr = jax.make_jaxpr(
        lambda p, c, f, ca, r: model_lib.decode_scan(
            p, cfg, c, f, ca, r, n_steps=n_steps, eos_id=1,
            temperature=0.7))(params, cur, fin, cache, rng)

    bodies = scan_bodies(jpr)
    findings: List[Finding] = []
    if not bodies:
        findings.append(_finding(
            "JX001", "decode_scan",
            "decode_scan traced without a scan equation — the decode "
            "chunk is no longer a device-resident lax.scan"))
    effects = [p for b in bodies for p in host_effect_prims(b)]
    for prim in sorted(set(effects)):
        findings.append(_finding(
            "JX001", "decode_scan",
            f"host-effect primitive '{prim}' inside the scanned decode "
            f"body (the chunk contract allows one host sync per chunk, "
            f"at the boundary)"))
    wide = widenings(jpr, forbidden)
    for dt in sorted(set(wide)):
        findings.append(_finding(
            "JX003", "decode_scan",
            f"convert_element_type to {dt} on the decode hot path"))
    stats = {"scan_eqns": len(bodies),
             "body_eqns": sum(len(b.eqns) for b in bodies),
             "host_effects": len(effects), "widenings": len(wide)}
    return findings, stats


def audit_prefill() -> Tuple[List[Finding], Dict[str, object]]:
    """Trace the chunked-prefill entry point; it must be host-effect-free
    (the scheduler owns its one sync, after the traced region)."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib

    cfg = _tiny_cfg()
    B, P_chunk = 2, 16
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    cache = model_lib.init_cache(cfg, batch=B, max_seq=cfg.max_seq_len,
                                 dtype=jnp.float32)
    toks = jnp.zeros((B, P_chunk), jnp.int32)
    n_valid = jnp.full((B,), P_chunk, jnp.int32)

    jpr = jax.make_jaxpr(
        lambda p, t, ca, nv: model_lib.prefill_chunk(
            p, cfg, {"tokens": t}, ca, nv))(params, toks, cache, n_valid)

    findings: List[Finding] = []
    effects = host_effect_prims(jpr)
    for prim in sorted(set(effects)):
        findings.append(_finding(
            "JX001", "prefill_chunk",
            f"host-effect primitive '{prim}' in the chunked-prefill "
            f"trace"))
    stats = {"eqns": sum(1 for _ in iter_eqns(jpr)),
             "host_effects": len(effects)}
    return findings, stats


def audit_train() -> Tuple[List[Finding], Dict[str, object]]:
    """Trace the train step's forward+backward (value_and_grad of loss_fn);
    it must be host-effect-free."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib

    cfg = _tiny_cfg()
    B, S = 2, 32
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jnp.zeros((B, S), jnp.int32),
        "labels": jnp.zeros((B, S), jnp.int32),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }

    def loss(p, b):
        total, _ = model_lib.loss_fn(p, cfg, b, ctx=None)
        return total

    jpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, batch)

    findings: List[Finding] = []
    effects = host_effect_prims(jpr)
    for prim in sorted(set(effects)):
        findings.append(_finding(
            "JX001", "train_step",
            f"host-effect primitive '{prim}' in the train fwd/bwd trace"))
    stats = {"eqns": sum(1 for _ in iter_eqns(jpr)),
             "host_effects": len(effects)}
    return findings, stats


def run_audit() -> AuditResult:
    """Run every jaxpr audit; the driver merges these findings with the
    AST layer's."""
    findings: List[Finding] = []
    stats: Dict[str, Dict[str, object]] = {}
    for name, fn in (("sp_causal", audit_sp_causal),
                     ("sp_exact", audit_sp_exact),
                     ("decode_scan", audit_decode),
                     ("prefill_chunk", audit_prefill),
                     ("train_step", audit_train)):
        f, s = fn()
        findings.extend(f)
        stats[name] = s
    return AuditResult(findings=findings, stats=stats)
