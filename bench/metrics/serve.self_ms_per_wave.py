"""The serve layer's self time: host milliseconds the clients spent inside
calls into the server that no stage counter covers, per read wave
delivered in the window.  The benchmark's host time inside server calls
less every stage second of ``CheckoutStats`` (plan, launch, pin,
stragglers, device wait, device->host copy, ingest staging, journal,
superblock refresh).  Each stage is counted in the call that spent it:
a read wave's dispatch stages at dispatch, its wait and copy at
delivery, a commit wave's stages when it lands."""

STAGES = ("plan_s", "launch_s", "pin_s", "straggler_s", "device_wait_s",
          "d2h_s", "ingest_stage_s", "journal_s", "refresh_s")


def read(ctx):
    waves = ctx.stats.get("waves_delivered", 0)
    if not waves or any(k not in ctx.stats for k in STAGES):
        return None
    staged = sum(ctx.stats[k] for k in STAGES)
    return (ctx.call_s - staged) / waves * 1e3
