"""DAP HTTP API over aiohttp.

The analog of the trillium router (reference:
aggregator/src/aggregator/http_handlers.rs:283-357): all DAP routes, CORS
preflight for browser clients, RFC 7807 problem documents on errors, and
bearer/DAP-Auth-Token extraction.  Routes:

    GET    /hpke_config?task_id=...
    PUT    /tasks/:task_id/reports
    PUT    /tasks/:task_id/aggregation_jobs/:aggregation_job_id
    POST   /tasks/:task_id/aggregation_jobs/:aggregation_job_id
    DELETE /tasks/:task_id/aggregation_jobs/:aggregation_job_id
    PUT    /tasks/:task_id/collection_jobs/:collection_job_id
    POST   /tasks/:task_id/collection_jobs/:collection_job_id
    DELETE /tasks/:task_id/collection_jobs/:collection_job_id
    POST   /tasks/:task_id/aggregate_shares
"""

from __future__ import annotations

import json
import logging
from typing import Optional

from aiohttp import web

from ..core.auth_tokens import DAP_AUTH_HEADER, AuthenticationToken
from ..core.trace import trace_phase
from ..datastore.datastore import DatastoreUnavailable
from ..messages import (
    AggregateShare,
    AggregationJobId,
    AggregationJobResp,
    CollectionJobId,
    HpkeConfigList,
    Report,
    TaskId,
)
from ..messages.codec import CodecError
from ..messages.problem_type import problem_document
from .aggregator import Aggregator
from .error import AggregatorError, DeletedCollectionJob

logger = logging.getLogger("janus_tpu.http")

PROBLEM_CONTENT_TYPE = "application/problem+json"


def _extract_auth(request: web.Request) -> Optional[AuthenticationToken]:
    """Bearer header first, then DAP-Auth-Token
    (reference: core/src/auth_tokens.rs)."""
    auth = request.headers.get("Authorization")
    if auth and auth.startswith("Bearer "):
        try:
            return AuthenticationToken.new_bearer(auth[len("Bearer ") :])
        except ValueError:
            return None
    dap = request.headers.get(DAP_AUTH_HEADER)
    if dap:
        try:
            return AuthenticationToken.new_dap_auth(dap)
        except ValueError:
            return None
    return None


def _problem(err: AggregatorError, task_id: Optional[TaskId]) -> web.Response:
    headers = (
        {"Retry-After": str(err.retry_after)}
        if err.retry_after is not None
        else None
    )
    if err.problem is None:
        return web.Response(
            status=err.status, text=err.detail or "", headers=headers
        )
    doc = problem_document(err.problem, task_id=task_id, detail=err.detail or None)
    return web.Response(
        status=err.status,
        content_type=PROBLEM_CONTENT_TYPE,
        text=json.dumps(doc),
        headers=headers,
    )


def _wire(body: bytes, media_type: str, status: int = 200) -> web.Response:
    return web.Response(status=status, body=body, content_type=media_type)


async def _maybe_taskprov(request: web.Request, task_id: TaskId) -> None:
    """In-band task provisioning (reference: aggregator.rs:722).  Upload and
    hpke_config requests are client-originated and cannot carry the peer
    token; everything else must."""
    taskprov_header = request.headers.get("dap-taskprov")
    if not taskprov_header:
        return
    from ..messages.dap import _unb64url

    aggregator = request.app["aggregator"]
    try:
        encoded = _unb64url(taskprov_header)
    except Exception:
        from .error import InvalidMessage

        raise InvalidMessage("malformed dap-taskprov header")
    client_route = request.path.endswith("/reports") or request.path.endswith(
        "/hpke_config"
    )
    await aggregator.ensure_taskprov_task(
        task_id,
        encoded,
        _extract_auth(request),
        require_peer_auth=not client_route,
    )


def _route(handler):
    """Wrap a handler: task-id parsing, error → problem-document mapping,
    per-route request metrics, and trace-context adoption — the peer's
    ``traceparent`` header (W3C trace id, sent by the leader's drivers) is
    bound for the request so helper-side logs and chrome-trace spans join
    the job's cross-process timeline (reference: http_handlers.rs error
    mapping + instrumented spans + :225-281 route counters)."""
    import time as _time

    from ..core.metrics import GLOBAL_METRICS
    from ..core.trace import parse_traceparent, trace_scope, trace_span

    async def wrapped(request: web.Request) -> web.Response:
        t0 = _time.monotonic()
        route = request.match_info.route.resource
        route_name = route.canonical if route else request.path
        with trace_scope(
            trace_id=parse_traceparent(request.headers.get("traceparent"))
        ), trace_span(
            "http_request", cat="http", method=request.method, route=route_name
        ):
            resp = await _wrapped_inner(request)
        GLOBAL_METRICS.observe_http(
            route_name,
            resp.status,
            _time.monotonic() - t0,
        )
        return resp

    async def _wrapped_inner(request: web.Request) -> web.Response:
        task_id = None
        try:
            if "task_id" in request.match_info:
                try:
                    task_id = TaskId.from_str(request.match_info["task_id"])
                except Exception:
                    from .error import InvalidMessage

                    raise InvalidMessage("malformed task id")
                from ..core.trace import bind_trace

                bind_trace(task_id=task_id)
                # in-band task provisioning (reference: aggregator.rs:722)
                await _maybe_taskprov(request, task_id)
            return await handler(request, task_id)
        except DeletedCollectionJob:
            return web.Response(status=204)
        except AggregatorError as err:
            return _problem(err, task_id)
        except CodecError as err:
            from .error import InvalidMessage

            return _problem(InvalidMessage(str(err)), task_id)
        except DatastoreUnavailable as err:
            # Datastore unreachable / retries exhausted is a TRANSIENT
            # infrastructure failure, not a protocol error: answer with
            # the DAP-retryable 503 (+ Retry-After) so the leader's
            # lease machinery redelivers — a split-brain window (helper
            # HTTP up, helper datastore down) must not 500 jobs into
            # their failure budget.  Scoped to the retries-exhausted
            # subclass: permanent DatastoreErrors (missing rows, schema
            # mismatch) would retry forever under a 503.
            logger.warning("datastore unavailable in %s: %s", request.path, err)
            return web.Response(
                status=503,
                text="datastore unavailable",
                headers={"Retry-After": "5"},
            )
        except Exception:
            logger.exception("internal error in %s", request.path)
            return web.Response(status=500, text="internal error")

    return wrapped


def aggregator_app(aggregator: Aggregator) -> web.Application:
    """Build the DAP service (reference: http_handlers.rs:283
    aggregator_handler)."""

    @_route
    async def hpke_config(request: web.Request, _tid) -> web.Response:
        task_id = None
        if "task_id" in request.query:
            try:
                task_id = TaskId.from_str(request.query["task_id"])
            except Exception:
                from .error import InvalidMessage

                raise InvalidMessage("malformed task id")
            await _maybe_taskprov(request, task_id)
        config_list = await aggregator.handle_hpke_config(task_id)
        return _wire(config_list.get_encoded(), HpkeConfigList.MEDIA_TYPE)

    @_route
    async def upload(request: web.Request, task_id) -> web.Response:
        body = await request.read()
        report = Report.get_decoded(body)
        await aggregator.handle_upload(task_id, report)
        return web.Response(status=201)

    @_route
    async def aggregation_job_put(request: web.Request, task_id) -> web.Response:
        job_id = AggregationJobId.from_str(request.match_info["aggregation_job_id"])
        body = await request.read()
        resp = await aggregator.handle_aggregate_init(
            task_id, job_id, body, _extract_auth(request)
        )
        with trace_phase("helper_init", "encode_resp", "python"):
            encoded = resp.get_encoded()
        return _wire(encoded, AggregationJobResp.MEDIA_TYPE)

    @_route
    async def aggregation_job_post(request: web.Request, task_id) -> web.Response:
        job_id = AggregationJobId.from_str(request.match_info["aggregation_job_id"])
        body = await request.read()
        resp = await aggregator.handle_aggregate_continue(
            task_id, job_id, body, _extract_auth(request)
        )
        return _wire(resp.get_encoded(), AggregationJobResp.MEDIA_TYPE)

    @_route
    async def aggregation_job_delete(request: web.Request, task_id) -> web.Response:
        job_id = AggregationJobId.from_str(request.match_info["aggregation_job_id"])
        await aggregator.handle_aggregate_delete(task_id, job_id, _extract_auth(request))
        return web.Response(status=204)

    @_route
    async def collection_job_put(request: web.Request, task_id) -> web.Response:
        job_id = CollectionJobId.from_str(request.match_info["collection_job_id"])
        body = await request.read()
        await aggregator.handle_create_collection_job(
            task_id, job_id, body, _extract_auth(request)
        )
        return web.Response(status=201)

    @_route
    async def collection_job_post(request: web.Request, task_id) -> web.Response:
        job_id = CollectionJobId.from_str(request.match_info["collection_job_id"])
        collection = await aggregator.handle_get_collection_job(
            task_id, job_id, _extract_auth(request)
        )
        if collection is None:
            return web.Response(
                status=202,
                headers={"Retry-After": str(aggregator.config.collection_job_retry_after)},
            )
        from ..messages import Collection

        return _wire(collection.get_encoded(), Collection.MEDIA_TYPE)

    @_route
    async def collection_job_delete(request: web.Request, task_id) -> web.Response:
        job_id = CollectionJobId.from_str(request.match_info["collection_job_id"])
        await aggregator.handle_delete_collection_job(
            task_id, job_id, _extract_auth(request)
        )
        return web.Response(status=204)

    @_route
    async def aggregate_shares(request: web.Request, task_id) -> web.Response:
        body = await request.read()
        share = await aggregator.handle_aggregate_share(
            task_id, body, _extract_auth(request)
        )
        return _wire(share.get_encoded(), AggregateShare.MEDIA_TYPE)

    async def healthz(_request: web.Request) -> web.Response:
        return web.Response(text="ok")

    async def metrics(_request: web.Request) -> web.Response:
        from ..core.metrics import GLOBAL_METRICS

        return web.Response(
            body=GLOBAL_METRICS.export(), content_type="text/plain"
        )

    async def cors_preflight(_request: web.Request) -> web.Response:
        # reference: http_handlers.rs CORS preflight for upload from browsers
        return web.Response(
            status=204,
            headers={
                "Access-Control-Allow-Origin": "*",
                "Access-Control-Allow-Methods": "PUT, POST, GET",
                "Access-Control-Allow-Headers": "content-type",
            },
        )

    app = web.Application(client_max_size=64 * 1024 * 1024)
    app["aggregator"] = aggregator
    app.add_routes(
        [
            web.get("/hpke_config", hpke_config),
            web.get("/healthz", healthz),
            web.get("/metrics", metrics),
            web.put("/tasks/{task_id}/reports", upload),
            web.options("/tasks/{task_id}/reports", cors_preflight),
            web.put(
                "/tasks/{task_id}/aggregation_jobs/{aggregation_job_id}",
                aggregation_job_put,
            ),
            web.post(
                "/tasks/{task_id}/aggregation_jobs/{aggregation_job_id}",
                aggregation_job_post,
            ),
            web.delete(
                "/tasks/{task_id}/aggregation_jobs/{aggregation_job_id}",
                aggregation_job_delete,
            ),
            web.put(
                "/tasks/{task_id}/collection_jobs/{collection_job_id}",
                collection_job_put,
            ),
            web.post(
                "/tasks/{task_id}/collection_jobs/{collection_job_id}",
                collection_job_post,
            ),
            web.delete(
                "/tasks/{task_id}/collection_jobs/{collection_job_id}",
                collection_job_delete,
            ),
            web.post("/tasks/{task_id}/aggregate_shares", aggregate_shares),
        ]
    )
    return app
