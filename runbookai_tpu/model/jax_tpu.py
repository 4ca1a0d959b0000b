"""The ``jax-tpu`` LLM provider: agent seam → in-tree serving engine.

This is THE replacement seam (SURVEY.md §2.2): where the reference's
``PiAIClient`` posts to hosted provider HTTP APIs, this client renders the
Llama-3 chat template, submits to the continuous-batching engine, and parses
tool calls / JSON out of the decoded text. ``complete()`` uses guided JSON
decoding so the structured orchestrator receives schema-parseable output.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from runbookai_tpu.agent.types import LLMResponse
from runbookai_tpu.engine.async_engine import AsyncEngine
from runbookai_tpu.engine.engine import (
    EngineConfig,
    EngineCore,
)
from runbookai_tpu.engine.request import SamplingParams
from runbookai_tpu.model.chat_template import (
    build_chat_prompt,
    build_completion_prompt,
    format_for_model,
    parse_assistant_output,
)
from runbookai_tpu.model.client import BaseLLMClient
from runbookai_tpu.model.guided import JsonMaskProvider
from runbookai_tpu.model.schema_guided import orchestrator_schemas
from runbookai_tpu.models.hf_loader import load_or_init
from runbookai_tpu.utils.tokens import load_tokenizer


async def stream_text(engine, tokenizer, prompt_ids, sampling,
                      state: Optional[dict] = None, priority: int = 0,
                      adapter: Optional[str] = None,
                      request_sink: Optional[list] = None,
                      request_id: Optional[str] = None):
    """Token stream -> text-piece stream, shared by every streaming surface
    (client ``chat_stream``, OpenAI SSE endpoint): incremental UTF-8 decode
    over per-token bytes (multi-byte chars split across tokens never yield
    mojibake) and stop-token skipping, mirroring ``EngineCore.output_for``.
    ``state`` (optional dict) receives ``n_tokens`` / ``saw_stop`` for
    finish-reason reporting."""
    import codecs

    stop_ids = {tokenizer.eot_id, tokenizer.eos_id}
    decoder = codecs.getincrementaldecoder("utf-8")("replace")
    async for tok in engine.generate_stream(prompt_ids, sampling,
                                            priority=priority,
                                            adapter=adapter,
                                            request_sink=request_sink,
                                            request_id=request_id):
        if state is not None:
            state["n_tokens"] = state.get("n_tokens", 0) + 1
        if tok in stop_ids:
            if state is not None:
                state["saw_stop"] = True
            continue
        piece = decoder.decode(tokenizer.id_to_bytes(tok))
        if piece:
            yield piece
    tail = decoder.decode(b"", final=True)
    if tail:
        yield tail


def _wire_supervisors(client, llm_cfg, fleets) -> None:
    """Attach + start one FleetSupervisor per AsyncFleet when
    ``llm.fleet.supervisor.enabled`` (chaos/supervisor.py): dead/wedged
    replicas are quarantined, their in-flight requests failed over
    through the router's retry path, the engine rebuilt online and
    rejoined with hysteresis. ``client.supervisors`` holds the running
    supervisors (daemon threads; ``/healthz`` reads their snapshots
    through each fleet's ``supervisor`` attach point)."""
    client.supervisors = []
    sup_cfg = getattr(getattr(llm_cfg, "fleet", None), "supervisor",
                      None)
    if sup_cfg is None or not getattr(sup_cfg, "enabled", False):
        return
    from runbookai_tpu.chaos import FleetSupervisor

    for fleet in fleets:
        client.supervisors.append(FleetSupervisor(
            fleet,
            poll_interval_s=sup_cfg.poll_interval_s,
            wedge_timeout_s=sup_cfg.wedge_timeout_s,
            rejoin_hysteresis_s=sup_cfg.rejoin_hysteresis_s,
            max_consecutive_rebuilds=sup_cfg.max_consecutive_rebuilds,
        ).start())


def _wire_tsdb(client, llm_cfg) -> None:
    """Attach + start the embedded time-series store (obs/tsdb.py) when
    ``llm.obs.tsdb.enabled``: a bounded ring over every exported
    ``runbook_*`` series, sampled from the live registry.
    ``GET /debug/query``, the ``/healthz`` ``history`` block and
    ``runbook query`` read it; the incident monitor (wired after this)
    derives its trend readings and bundle lookback from it. None when
    the obs layer or the store is disabled — zero ``runbook_tsdb_*``
    series and every surface on top reports itself absent."""
    from runbookai_tpu.obs.tsdb import MetricsTSDB

    store = MetricsTSDB.from_config(llm_cfg)
    if store is not None:
        client.tsdb = store.start()


def _wire_incidents(client, llm_cfg) -> None:
    """Attach + start the incident monitor (obs/incident.py) over every
    fleet the client serves through: it folds the exported signals (SLO
    burn, workload drift, replica health, supervisor states, router
    sheds/stale pulls, queue-wait percentiles) into an incident
    lifecycle and captures a content-hashed evidence bundle on every
    open (``llm.obs.incident_dir``). ``GET /debug/incidents``, the
    ``/healthz`` ``incidents`` block and ``runbook incident`` all read
    it; None when ``llm.obs`` (or ``incidents_enabled``) is off."""
    from runbookai_tpu.obs.incident import IncidentMonitor

    mm = client.multi_model
    fleets = ([g.fleet for g in mm.groups.values()] if mm is not None
              else [client.engine])
    monitor = IncidentMonitor.from_config(
        llm_cfg, fleets=fleets, cores=client.cores,
        slo_monitor=client.slo_monitor,
        workload_monitor=client.workload_monitor,
        tsdb=getattr(client, "tsdb", None))
    if monitor is not None:
        client.incident_monitor = monitor.start()


class JaxTpuClient(BaseLLMClient):
    def __init__(
        self,
        core: "EngineCore | list[EngineCore]",
        tokenizer,
        temperature: float = 0.0,
        top_p: float = 1.0,
        top_k: int = 0,
        max_new_tokens: int = 1024,
        guided_json: bool = True,
        chat_format: str = "llama3",
        fleet_cfg=None,
        slo_monitor=None,
        tenants=None,
        engine=None,
        workload_monitor=None,
    ):
        # ``core`` may be a data-parallel fleet (list of replicas, built by
        # engine/fleet.build_engine_fleet when EngineConfig.dp_replicas > 1):
        # the client then serves through an AsyncFleet with the same
        # generate/generate_stream surface, and ``self.core`` stays replica
        # 0 for surfaces that need the shared pieces (LoRA registry names,
        # tokenizer-adjacent config) — fleet-wide state goes through
        # ``self.engine.health_snapshot()``. ``fleet_cfg`` (a
        # fleet.FleetConfig) carries the router policy knobs.
        #
        # ``engine`` (prebuilt) overrides the construction below — the
        # multi-model path (llm.models) passes its MultiModelFleet here;
        # ``core``/``tokenizer``/``chat_format`` then describe the
        # DEFAULT group (what agent-side chat()/complete() serve against).
        cores = list(core) if isinstance(core, (list, tuple)) else [core]
        self.cores = cores
        self.core = cores[0]
        if engine is not None:
            self.engine = engine
        elif len(cores) > 1:
            from runbookai_tpu.engine.fleet import AsyncFleet

            self.engine = AsyncFleet(cores, fleet_cfg)
        else:
            self.engine = AsyncEngine(self.core)
        self.tokenizer = tokenizer
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.max_new_tokens = max_new_tokens
        self.guided_json = guided_json
        self.chat_format = chat_format
        # SLO monitor (utils/slo.py, built by from_config from llm.slo):
        # /healthz reads it for the live burn-ratio block; None when no
        # objective is configured (zero SLO surface).
        self.slo_monitor = slo_monitor
        # Tenant admission governor (sched/tenants.py, built by
        # from_config from llm.tenants): the OpenAI server gates every
        # chat/completions request through it BEFORE enqueue. None = no
        # tenant surface.
        self.tenants = tenants
        # Workload monitor (runbookai_tpu/obs, built by from_config from
        # llm.obs): live fingerprints + plan-drift + replica health.
        # /debug/workload, the /healthz workload block and the `runbook
        # workload` CLI all read it; None = zero workload surface.
        self.workload_monitor = workload_monitor
        # Incident monitor (obs/incident.py, wired by _wire_incidents in
        # from_config): detection + black-box capture. None = zero
        # incident surface (/debug/incidents reports itself disabled).
        self.incident_monitor = None
        # Embedded time-series store (obs/tsdb.py, wired by _wire_tsdb
        # in from_config): metric history + PromQL-lite queries. None =
        # zero history surface (/debug/query reports itself disabled,
        # /healthz has no history block, bundles no lookback).
        self.tsdb = None
        # Fleet supervisors (chaos/supervisor.py, wired by
        # _wire_supervisors in from_config); shutdown() stops them.
        self.supervisors: list = []

    # --------------------------------------------------------- model groups

    @property
    def multi_model(self):
        """The :class:`~runbookai_tpu.fleet.multimodel.MultiModelFleet`
        when this client serves ``llm.models``, else ``None`` — the
        server's duck-typing seam for model-field routing."""
        from runbookai_tpu.fleet.multimodel import MultiModelFleet

        return (self.engine
                if isinstance(self.engine, MultiModelFleet) else None)

    def engine_for(self, model=None):
        """The engine a resolved model group serves through (the group's
        AsyncFleet under ``llm.models``; the one engine otherwise)."""
        mm = self.multi_model
        return mm.engine_for(model) if mm is not None else self.engine

    def tokenizer_for(self, model=None):
        """Per-group tokenizer — multi-model requests must encode with
        the tokenizer of the model they route to."""
        mm = self.multi_model
        return (mm.group(model).tokenizer if mm is not None
                else self.tokenizer)

    def chat_format_for(self, model=None) -> str:
        mm = self.multi_model
        return (mm.group(model).chat_format if mm is not None
                else self.chat_format)

    # ------------------------------------------------------------- factories

    @classmethod
    def from_config(cls, llm_cfg) -> "JaxTpuClient":
        """Build engine + client from an ``LLMConfig`` (utils/config.py).

        The engine-construction path itself lives in
        ``runbookai_tpu.fleet.build.build_group`` — ONE place for plan
        application, weight discovery (configured ``model_path`` first,
        else ``$RUNBOOK_WEIGHTS``), mesh planning and core construction,
        shared with the multi-model fleet so the two cannot drift.

        ``llm.plan`` makes a ``runbook tune`` serving-plan artifact a
        first-class config input: the plan's engine block supplies every
        knob the sweep decided, while keys the operator set EXPLICITLY in
        YAML keep winning (``autotune.plan.apply_plan_to_llm`` reads
        pydantic's ``model_fields_set`` for exactly that precedence), and
        plan keys with no YAML spelling (speculative, mixed_token_budget,
        …) land directly on the built EngineConfig.

        ``llm.models`` switches to the multi-model fleet
        (``runbookai_tpu/fleet``): one client whose ``engine`` is a
        :class:`~runbookai_tpu.fleet.multimodel.MultiModelFleet`; the
        agent-side ``chat``/``complete`` surface serves against the
        FIRST group (the default model), while the OpenAI server routes
        every request by its ``model`` field."""
        from runbookai_tpu.fleet.build import (
            build_group,
            build_multi_model_fleet,
            wire_feedback,
        )

        slo_monitor = None
        if getattr(llm_cfg, "slo", None) is not None:
            from runbookai_tpu.utils.slo import SLOMonitor

            # None when llm.slo sets no objective: an unconfigured run
            # must export zero runbook_slo_* series.
            slo_monitor = SLOMonitor.from_config(llm_cfg.slo)
        tenants = None
        if getattr(llm_cfg, "tenants", None) is not None:
            from runbookai_tpu.sched import TenantGovernor

            # None when llm.tenants is absent/disabled: zero tenant
            # surface, the server admits everything exactly as before.
            tenants = TenantGovernor.from_config(llm_cfg.tenants)
        def build_workload_monitor(cores=None, multi_model=None):
            # llm.obs (runbookai_tpu/obs): None when disabled — zero
            # workload surface, no runbook_workload_* series.
            from runbookai_tpu.obs import WorkloadMonitor

            return WorkloadMonitor.from_config(
                llm_cfg, cores=cores, multi_model=multi_model,
                slo_monitor=slo_monitor, tenants=tenants)

        if getattr(llm_cfg, "models", None):
            engine = build_multi_model_fleet(llm_cfg,
                                             slo_monitor=slo_monitor)
            default = engine.groups[engine.default]
            client = cls(
                engine.cores, default.tokenizer,
                temperature=llm_cfg.temperature, top_p=llm_cfg.top_p,
                top_k=llm_cfg.top_k,
                max_new_tokens=llm_cfg.max_new_tokens,
                guided_json=llm_cfg.guided_json,
                chat_format=default.chat_format,
                slo_monitor=slo_monitor, tenants=tenants, engine=engine,
                workload_monitor=build_workload_monitor(multi_model=engine))
            _wire_supervisors(client, llm_cfg,
                              [g.fleet for g in engine.groups.values()])
            _wire_tsdb(client, llm_cfg)
            _wire_incidents(client, llm_cfg)
            return client
        built = build_group(llm_cfg)
        wire_feedback(built.cores, built.llm_cfg, slo_monitor)
        client = cls(
            built.cores if len(built.cores) > 1 else built.cores[0],
            built.tokenizer,
            temperature=llm_cfg.temperature, top_p=llm_cfg.top_p,
            top_k=llm_cfg.top_k,
            max_new_tokens=llm_cfg.max_new_tokens,
            guided_json=llm_cfg.guided_json,
            chat_format=built.chat_format,
            fleet_cfg=built.fleet_cfg,
            slo_monitor=slo_monitor,
            tenants=tenants,
            workload_monitor=build_workload_monitor(cores=built.cores),
        )
        from runbookai_tpu.engine.fleet import AsyncFleet

        if isinstance(client.engine, AsyncFleet):
            _wire_supervisors(client, llm_cfg, [client.engine])
        _wire_tsdb(client, llm_cfg)
        _wire_incidents(client, llm_cfg)
        return client

    @classmethod
    def for_testing(cls, model_name: str = "llama3-test",
                    temperature: float = 0.0, max_new_tokens: int = 32,
                    max_seq_len: int = 256, schema_limits=None,
                    lora_registry=None, **engine_kw) -> "JaxTpuClient":
        """Tiny random-init client on the byte tokenizer (CPU tests)."""
        tokenizer = load_tokenizer(None)
        cfg, params = load_or_init(model_name, None, dtype=jnp.float32)
        ecfg_kw = dict(page_size=4, num_pages=256, max_batch_slots=4,
                       prefill_chunk=32, max_seq_len=max_seq_len,
                       kv_dtype=jnp.float32)
        ecfg_kw.update(engine_kw)  # tests may override any default
        ecfg = EngineConfig(**ecfg_kw)
        masker = JsonMaskProvider(tokenizer, schemas=orchestrator_schemas(),
                                  limits=schema_limits)
        if ecfg.dp_replicas > 1:
            from runbookai_tpu.engine.fleet import build_engine_fleet

            core = build_engine_fleet(
                cfg, params, tokenizer, ecfg,
                mask_fn=masker.mask, advance_fn=masker.advance,
                lora_registry=lora_registry)
        else:
            core = EngineCore(cfg, params, tokenizer, ecfg,
                              mask_fn=masker.mask, advance_fn=masker.advance,
                              lora_registry=lora_registry)
        return cls(core, tokenizer, temperature=temperature,
                   max_new_tokens=max_new_tokens,
                   chat_format=format_for_model(model_name, cfg.family))

    # ------------------------------------------------------------------- API

    def _sampling(self, guided: Optional[str] = None, max_new: Optional[int] = None) -> SamplingParams:
        return SamplingParams(
            temperature=self.temperature,
            top_p=self.top_p,
            top_k=self.top_k,
            max_new_tokens=max_new or self.max_new_tokens,
            stop_token_ids=(self.tokenizer.eot_id, self.tokenizer.eos_id),
            guided=guided,
        )

    async def chat(self, system_prompt, user_prompt, tools=None) -> LLMResponse:
        prompt = build_chat_prompt(system_prompt, user_prompt, tools,
                                   fmt=self.chat_format)
        ids = self.tokenizer.encode(prompt)
        out = await self.engine.generate(ids, self._sampling())
        content, tool_calls, thinking = parse_assistant_output(out.text)
        return LLMResponse(
            content=content,
            tool_calls=tool_calls,
            thinking=thinking,
            usage={
                "prompt_tokens": len(ids),
                "completion_tokens": out.decode_tokens,
                "ttft_ms": int(out.ttft_ms or 0),
            },
        )

    async def chat_stream(self, system_prompt, user_prompt, tools=None):
        """TRUE token streaming override of the BaseLLMClient fallback
        (which chunks a completed response). Yields the same event-dict
        protocol: ``{"type": "text", "delta"}`` per decoded piece, then
        parsed ``tool_call`` events, then ``{"type": "done", "response"}``.

        Divergence from the fallback, by design: text deltas are the RAW
        model output as sampled (tool-call/thinking markup included — it
        cannot be parsed out until the document completes), while
        ``done.response.content`` is the parsed content, exactly as
        :meth:`chat` returns it. Consumers that must render only parsed
        content should buffer until ``done``.

        Text decoding/stop handling is the shared :func:`stream_text`
        (also behind the OpenAI SSE endpoint).
        """
        prompt = build_chat_prompt(system_prompt, user_prompt, tools,
                                   fmt=self.chat_format)
        ids = self.tokenizer.encode(prompt)
        state: dict = {}
        parts: list[str] = []
        async for piece in stream_text(self.engine, self.tokenizer, ids,
                                       self._sampling(), state=state):
            parts.append(piece)
            yield {"type": "text", "delta": piece}
        content, tool_calls, thinking = parse_assistant_output("".join(parts))
        for call in tool_calls:
            yield {"type": "tool_call", "call": call}
        yield {"type": "done", "response": LLMResponse(
            content=content, tool_calls=tool_calls, thinking=thinking,
            usage={"prompt_tokens": len(ids),
                   "completion_tokens": state.get("n_tokens", 0)})}

    def _completion_request(self, prompt: str, guided: Optional[bool],
                            schema: Optional[str]):
        """(ids, sampling) for a completion — ONE place for the guided
        default / prompt build / grammar pick, so the buffered and
        streaming paths cannot drift (their text must stay identical)."""
        use_guided = self.guided_json if guided is None else guided
        ids = self.tokenizer.encode(
            build_completion_prompt(prompt, fmt=self.chat_format))
        grammar = (schema or "json") if use_guided else None
        return ids, self._sampling(guided=grammar)

    async def complete(self, prompt: str, guided: Optional[bool] = None,
                       schema: Optional[str] = None) -> str:
        """Plain completion; guided JSON masking on by default (config) since
        every orchestrator prompt expects a JSON document back. ``schema``
        names a compiled grammar (``"triage"``, ``"evaluation"``, … — see
        :func:`~runbookai_tpu.model.schema_guided.orchestrator_schemas`)
        that constrains the output to exactly that document shape."""
        ids, sampling = self._completion_request(prompt, guided, schema)
        out = await self.engine.generate(ids, sampling)
        return out.text

    async def complete_stream(self, prompt: str,
                              guided: Optional[bool] = None,
                              schema: Optional[str] = None):
        """Streaming twin of :meth:`complete`: yields text deltas as the
        engine samples (grammar fast-forwarded runs arrive as one burst).
        The orchestrator uses it to paint phase documents live under the
        hypothesis tree."""
        ids, sampling = self._completion_request(prompt, guided, schema)
        async for piece in stream_text(self.engine, self.tokenizer, ids,
                                       sampling):
            yield piece

    def runtime_info(self) -> dict:
        """What this process actually serves on, as resolved — the
        ``runtime`` block of ``/healthz``. Device facts are JAX's own;
        the implementations are the engine's config AFTER its static
        rules and kernel probes (``EngineCore.__init__``), not what the
        YAML asked for."""
        import jax

        from runbookai_tpu.engine.hlo_bytes import (
            kv_pool_nbytes,
            param_nbytes,
        )
        from runbookai_tpu.models.quant import is_quantized
        from runbookai_tpu.native import NativePageAllocator

        core = self.core
        devices = jax.devices()

        def device_row(d) -> dict:
            stats = d.memory_stats() or {}  # the CPU backend reports none
            return {"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit")}

        quantized = any(is_quantized(v)
                        for v in core.params["layers"].values())
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "devices": [device_row(d) for d in devices],
            "model": core.cfg.name,
            "n_layers": core.cfg.n_layers,
            "weight_dtype": ("int8" if quantized
                             else str(core.params["embed"].dtype)),
            "kv_dtype": jnp.dtype(core.ecfg.kv_dtype).name,
            "attn_impl": core.ecfg.attn_impl,
            "qmm_impl": core.ecfg.qmm_impl,
            "mixed_dispatch": core._mixed,
            "overlap_decode": core.ecfg.overlap_decode,
            "allocator": ("native" if isinstance(core.kv.allocator,
                                                 NativePageAllocator)
                          else "python"),
            # Per engine replica, summed over the devices it spans.
            "weight_bytes": param_nbytes(core.params),
            "kv_pool_bytes": kv_pool_nbytes(core),
            # The slot-indexed pool of recurrent state and its snapshot
            # pool (0 for a model whose state is all pages).
            "state_pool_bytes": sum(
                leaf.nbytes
                for leaf in jax.tree.leaves((core._state, core._snaps))),
            # The window layers' pool (0 for a model without window
            # layers): part of ``kv_pool_bytes``, both sides.
            "kv_window_pool_bytes": sum(
                leaf.nbytes for side in (core._kv_k, core._kv_v)
                if isinstance(side, dict)
                for leaf in jax.tree.leaves(side["window"])),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            # Which devices hold each engine replica's KV pool.
            "replicas": [
                sorted(d.id for d in
                       jax.tree.leaves(c._kv_k)[0].devices())
                for c in self.cores],
        }

    async def shutdown(self) -> None:
        """Stop everything ``from_config`` started: the sampler threads
        (metric history, incident monitor), the fleet supervisors, then
        the engine loops — so the process that holds the chip can exit."""
        for stoppable in (self.tsdb, self.incident_monitor,
                          *self.supervisors):
            if stoppable is not None:
                stoppable.stop()
        await self.engine.stop()
