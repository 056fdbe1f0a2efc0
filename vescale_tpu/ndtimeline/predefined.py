"""Predefined metric names (reference legacy/vescale/ndtimeline/
predefined.py).

Every name here has a live call site (VERDICT item 7 contract — a test
greps for it).  The reference's p2p/collective span names (send/recv
forward/backward, unshard-all-gather, grad-reduce-scatter/all-reduce) are
deliberately ABSENT: on TPU those run inside the jitted step where a host
span cannot bracket them — the XLA profiler owns that timing."""

# pipe engine instruction spans (pipe/engine.py)
FORWARD_COMPUTE = "forward-compute"
BACKWARD_COMPUTE = "backward-compute"
WGRAD_COMPUTE = "weight-grad-compute"
# Spans a trace session's readers lay over the device trace carry the prefix
# ``vs.`` (ring metric and TraceAnnotation alike), which tells the program's
# spans from a caller's own annotations in the profiler's host lines.
# train loop (train.py) — host region around the call of the jitted step:
# the enqueue only (the caller waits for the result)
TRAIN_STEP = "vs.train-step"
# eager optimizer step (parallel/optimizer.py; in-jit steps are XLA's)
OPTIMIZER_STEP = "optimizer-step"
# native loader batch fetch (data/loader.py)
DATA_LOAD = "vs.data-load"
# checkpoint layer (checkpoint/__init__.py, manager.py)
CHECKPOINT_SAVE = "checkpoint-save"
CHECKPOINT_LOAD = "checkpoint-load"
CHECKPOINT_COMMIT = "checkpoint-commit"
# serve request lifecycle (serve/reqtrace.py emits; docs/observability.md
# "Request-span taxonomy").  Every request's chain is
#   submit -> [queue-wait -> prefill -> decode-token*]* -> terminal
# with an evict span marking each replay fork; the terminal span's
# ``outcome`` tag matches the scheduler ledger status exactly
# (reqtrace.verify_request_chains asserts the lockstep).
SERVE_SUBMIT = "serve-submit"
SERVE_QUEUE_WAIT = "serve-queue-wait"
SERVE_PREFILL = "serve-prefill"
SERVE_DECODE_STEP = "serve-decode-step"
SERVE_DECODE_TOKEN = "serve-decode-token"
SERVE_EVICT = "serve-evict"
SERVE_TERMINAL = "serve-terminal"
# serve engine and loop, live (``ndtimeit``, so they nest in the profiler's
# trace; the per-request spans above are recorded after the fact and are
# laid over it by a trace session's clock offset): the whole of
# ``ServeEngine.prefill`` / ``decode``; inside each, the wait for the device
# plus the logits' copy to the host; and in serve/loop.py the host's
# sampling between two decode calls
SERVE_PREFILL_CALL = "vs.serve-prefill"
SERVE_DECODE_CALL = "vs.serve-decode"
SERVE_PREFILL_FETCH = "vs.serve-prefill.fetch"
SERVE_DECODE_FETCH = "vs.serve-decode.fetch"
SERVE_SAMPLE = "vs.serve-sample"
# A LAUNCH HAS A NUMBER (``engine.launches``: one sequence for decode steps
# and prefills, a plain integer kept whether or not anything traces).  With a
# decode step in flight the spans above no longer bracket the program they
# started (``vs.serve-decode`` opens at launch k and closes when step k-1 is
# read), so the ENQUEUE alone has a span of its own inside each, tagged
# ``launch=<n>`` (a prefill's also ``rung`` and ``slot``), and the ``.fetch``
# that later waits for that program carries the same ``launch``: the span
# that caused it.  The tags are the event's stats in the profiler's trace,
# where a reader joins a launch to the device's program by kind and order
# (benchmark/layer_metrics/_programs.py).  A decode step that CARRIES a prompt
# (serve/engine.py, "a step that carries a prompt") is one launch of the
# decode kind that says ``rung`` and ``slot`` too, and no prefill launch.
SERVE_DECODE_LAUNCH = "vs.serve-decode.launch"
SERVE_PREFILL_LAUNCH = "vs.serve-prefill.launch"
# the serve loop's iteration, tiled (serve/loop.py; one call site each): what
# the top of an iteration does before admission (beat, fault hooks, control
# jobs, the verdicts that evict or cancel, ``_settle`` where a boundary forces
# it); from the inbox's drain through ``scheduler.admit`` to the first
# ``engine.prefill`` (tagged ``admitted=<n>``); ``_close_step`` whole, once a
# read step; the caller's ``on_step``; the idle slice's sleep (nothing active,
# nothing queued).  With ``vs.serve-sample``, ``vs.serve-decode`` and
# ``vs.serve-prefill`` they cover an iteration but for the loop's own lines.
SERVE_BOUNDARY = "vs.serve-boundary"
SERVE_ADMIT = "vs.serve-admit"
SERVE_BOOKS = "vs.serve-books"
SERVE_HOOK = "vs.serve-hook"
SERVE_IDLE = "vs.serve-idle"
# after the fact, beside serve-queue-wait and with the request's rid: from
# ``RequestInbox.push`` (which stamps the request as it queues it) to the
# loop's drain that took it.  inbox-wait + queue-wait + prefill tile a
# network-fed request's time to its first token.
SERVE_INBOX_WAIT = "serve-inbox-wait"
# the one annotation a trace session (ndtimeline/api.py) emits at its start:
# its instant is known on the spans' clock and on the trace's
SESSION_MARK = "vs.session-mark"
# the interpreter's collector, timed where it runs (telemetry/hoststat.py's
# witness on ``gc.callbacks``; ndtimeline/api.py makes the span of it while a
# session is armed): a collection of generation 1 or 2, tagged ``gen`` and
# ``collected``, nested in whatever span the host was in.  A full collection
# holds the interpreter's lock for its length, so a device left idle above
# one names its cause.
HOST_GC = "vs.host-gc"
# speculative decoding (serve/speculative.py; ISSUE 15): with a drafter
# armed each decode iteration forks into a serve-draft span (the drafter's
# k sequential proposal steps) and a serve-verify span (the target's ONE
# batched multi-token verify step, tagged with drafted/accepted counts and
# the running acceptance rate) — both host-lane per-step spans like
# serve-decode-step, no rid.
SERVE_DRAFT = "serve-draft"
SERVE_VERIFY = "serve-verify"
# fleet-router request journey (serve/fleettrace.py emits; docs/
# observability.md "Fleet tracing").  Every routed request's ROUTER-side
# chain is
#   fleet-submit -> fleet-dispatch-attempt[i]* (backoff forks between
#   attempts) -> fleet-terminal
# with the dispatch-attempt ``tag`` doubling as the trace context that
# rides the /submit wire: the replica's serve-submit span echoes it, so
# the fleet timeline assembler stitches router chains to replica chains
# by construction (fleettrace.assemble_fleet_timeline).
FLEET_SUBMIT = "fleet-submit"
FLEET_DISPATCH = "fleet-dispatch-attempt"
FLEET_BACKOFF = "fleet-backoff"
FLEET_BREAKER = "fleet-breaker"
FLEET_TERMINAL = "fleet-terminal"
# fleet self-operation (serve/autoscale.py + the serve loop's reload
# machine; fleettrace.scale_event / rollout_stage emit).  Every
# autoscaler decision (scale-up spawn, scale-down drain) and every
# rolling-rollout stage (drain / baseline / swap / canary / commit /
# rollback) lands as a span in the same streams the journeys live in, so
# the merged fleet timeline shows the fleet operating itself inline with
# the requests it affected.
FLEET_SCALE = "fleet-scale"
FLEET_ROLLOUT = "fleet-rollout-stage"
# router high availability (serve/journal.py + FleetRouter.recover_from_
# journal / StandbyRouter; fleettrace.recover_event / takeover_event
# emit).  One span per crash recovery (journal replay -> /outcomes
# harvest -> re-drive) and per warm-standby promotion, so the leaderless
# window and the reconstruction cost read inline on the fleet timeline.
FLEET_RECOVER = "fleet-recover"
FLEET_TAKEOVER = "fleet-takeover"
# alert-engine lifecycle (telemetry/alerts.py emits): a point span per
# transition plus, on resolve, one span covering the whole firing episode
# — so a Perfetto timeline shows the alert as a bar spanning exactly the
# degraded step/request spans beneath it (docs/observability.md
# "Reading an alert span").
ALERT = "alert"
