"""Pinned error-message constants shared across config validation and drivers.

Several ValueError messages in this repo are *pinned*: tests match on their
text (``pytest.raises(match=...)``) and more than one module raises them —
``FLConfig.__post_init__`` validates at construction, ``ota.check_uplink``
re-validates call-site uplink overrides inside every fl.py driver, and
``fl._horizon_setup`` guards direct ``run_horizon_scanned`` calls.  Before
this module each site carried its own literal copy, so a wording tweak in
one place silently desynchronized the others (the FLConfig /
``ota.check_uplink`` drift hazard).

The single source of truth lives here as ``.format()`` templates.  The
``flcheck`` static-analysis pass (rule FLC006, ``tools/flcheck``) enforces
centralization: a ``raise ValueError`` whose literal duplicates one of
these messages anywhere outside this module is a lint error — new call
sites must import the constant.

Adding a message: define an UPPER_CASE ``str`` constant (optionally with
``{field}`` / ``{field!r}`` placeholders).  flcheck parses this file with
``ast`` only (never imports it) and derives each constant's longest
placeholder-free fragment as the duplication signature, so no registration
step is needed.
"""
from __future__ import annotations

# --- uplink-combination rules (ota.check_uplink; FLConfig re-raises) -------

ERR_UNKNOWN_UPLINK = "unknown uplink {uplink!r}; known: {modes}"

ERR_OTA_TOPK = (
    "uplink='ota' cannot apply top-k sparsification: analog "
    "superposition transmits the raw update vector over the "
    "air, never a per-device coded payload; set topk=1.0"
)

ERR_OTA_COMPRESSION = (
    "uplink='ota' requires compression='none': the PS receives "
    "the noisy analog sum and never decodes per-device "
    "payloads, so DoReFa quantization cannot apply"
)

ERR_OTA_MAPEL = (
    "uplink='ota' cannot use power_mode='mapel': MAPEL "
    "optimizes SIC decode rates, which analog superposition "
    "never performs; use power_mode='max' or 'ota-align'"
)

ERR_OTA_ALIGN_UPLINK = (
    "power_mode='ota-align' requires uplink='ota': alignment "
    "powers implement truncated channel inversion for the analog "
    "sum and have no digital-uplink meaning"
)

# --- horizon / policy coherence (FLConfig + fl._horizon_setup) -------------

ERR_SCAN_ONLINE_POLICY = (
    "horizon='scan' cannot drive online policy "
    "{scheduler!r}: it does not implement the traced selection "
    "protocol (scheduling.SchedulerPolicy: traced_protocol = True "
    "+ init_traced/select_round_traced), so its FL-state feedback "
    "needs the host round loop; use horizon='per-round' or add "
    "the traced protocol"
)

ERR_SCAN_ONLINE_MAPEL = (
    "horizon='scan' with online policy {scheduler!r} cannot use "
    "power_mode='mapel': the polyblock search is host-iterative "
    "and cannot run inside the traced round body; use "
    "power_mode='max' (or 'ota-align' under uplink='ota')"
)

# --- device placement (fl.run_cell_sweep, rates_jax.greedy_rounds_fused) ---

ERR_SHARDS_EXCEED_DEVICES = (
    "{option}={requested} asks for {requested} devices, but only "
    "{available} local devices are present"
)

# --- Pallas kernels (kernels.platform) --------------------------------------

ERR_PALLAS_PLATFORM = (
    "Pallas kernels run on 'tpu' (Mosaic) or 'cpu' (interpreter), not on "
    "{platform!r}; pass interpret= explicitly or use the XLA path"
)
