"""The two frontend kinds a configuration selects: ``serve`` (one
ServeFrontend) and ``fleet`` (FleetFrontend over local one-chip replicas).

This is the only file that touches the program: it builds the system under
test from the configuration's data and gives the generators one small
surface (open_stream / submit / poll) plus the program's own counters.
"""


class Front:
    """The system under test behind open_stream / submit / poll."""

    kind = None
    step_name = "jit_step"   # the Engine's jitted step, as the trace names it

    def __init__(self, cell, params):
        self.cell = cell
        self.params = params
        self.fe = None

    # -- built by the kinds ------------------------------------------------

    def _filter(self):
        from dvf_tpu.ops import get_filter

        spec = self.cell.config["filter"]
        kwargs = dict(spec["kwargs"])
        if self.params is not None:
            # A host copy: Engine.compile donates the state it is given, so
            # device arrays handed in as ``params`` would be deleted by its
            # warm-up step (PERF.md, open questions). The tree is 7 MB.
            import jax
            import numpy as np

            kwargs["params"] = jax.tree.map(np.asarray, self.params)
        return get_filter(spec["name"], **kwargs)

    def _serve_config(self):
        from dvf_tpu.serve import ServeConfig

        return ServeConfig(**self.cell.config["serve"])

    # -- the surface the generators drive -----------------------------------

    def open_stream(self, slo_ms):
        return self.fe.open_stream(frame_shape=self.cell.frame_shape, slo_ms=slo_ms)

    def submit(self, sid, frame, ts):
        return self.fe.submit(sid, frame, ts=ts)

    def poll(self, sid):
        return self.fe.poll(sid)

    def stop(self):
        if self.fe is not None:
            self.fe.stop()
            self.fe = None


class ServeFront(Front):
    kind = "serve"

    def start(self):
        from dvf_tpu.serve import ServeFrontend

        self.fe = ServeFrontend(self._filter(), self._serve_config()).start()
        return self

    def replicas(self):
        return 1

    def health(self):
        h = self.fe.health()
        return h["ok"], h["error"]

    def counters(self):
        """{"buckets": [bucket rows], "sessions": {sid: row},
        "replica_frames": None} from ServeFrontend.stats()."""
        st = self.fe.stats()
        return {"buckets": [r for r in st["buckets"].values() if r.get("batches")],
                "sessions": st["sessions"], "errors": st["errors"],
                "faults": st["faults"]["by_kind"], "replica_frames": None}


class FleetFront(Front):
    kind = "fleet"

    def start(self):
        from dvf_tpu.fleet import FleetConfig, FleetFrontend

        f = self.cell.config["fleet"]
        cfg = FleetConfig(replicas=f["replicas"], mode=f["mode"],
                          devices_per_replica=f["devices_per_replica"],
                          serve=self._serve_config())
        self.fe = FleetFrontend(self._filter(), cfg).start()
        return self

    def replicas(self):
        return self.cell.config["fleet"]["replicas"]

    def health(self):
        st = self.fe.stats()
        bad = {rid: r["state"] for rid, r in st["replicas"].items()
               if r["state"] != "healthy" or r.get("errors")}
        return not bad and not st["replica_losses"], (bad or None)

    def counters(self):
        # fleet.stats() gives per-replica totals but not the replicas'
        # bucket rows (ingest/egress times); those are read from each
        # replica's own stats_full() (PERF.md, open questions).
        buckets, sessions, frames, errors, faults = [], {}, [], 0, {}
        for rid, r in sorted(self.fe._replicas.items()):
            st = r.stats_full()["stats"]
            buckets += [b for b in st["buckets"].values() if b.get("batches")]
            sessions.update(st["sessions"])
            frames.append(sum(s["delivered"] for s in st["sessions"].values()))
            errors += st["errors"]
            for k, v in st["faults"]["by_kind"].items():
                faults[k] = faults.get(k, 0) + v
        fs = self.fe.stats()
        lost = sum(s["lost"] for s in fs["sessions"].values())
        if lost or fs["order_violations"]:
            faults["fleet_lost_or_disordered"] = lost + fs["order_violations"]
        return {"buckets": buckets, "sessions": sessions, "errors": errors,
                "faults": faults, "replica_frames": frames}


KINDS = {"serve": ServeFront, "fleet": FleetFront}


def build(cell, params):
    kind = cell.config["frontend"]
    if kind not in KINDS:
        raise SystemExit(f"chipbench: frontend kind {kind!r} (known: {sorted(KINDS)})")
    return KINDS[kind](cell, params)
