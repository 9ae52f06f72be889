"""The load generator: an asyncio HTTP client in a process of its own.

It sends each request of a plan when it is due, reads the answer as a
server-sent event stream, and keeps for each request when it was due, when
it was really sent and when each chunk arrived. It never looks at the
server's own clocks. Bodies are serialised before the clock starts, so that
inside the window the loop only sleeps, writes and reads.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time

import aiohttp

from .traffic import Plan, Request


@dataclasses.dataclass
class Record:
    idx: int
    scored: bool
    prompt_tokens: int
    max_tokens: int
    due: float  # seconds on the client's clock, relative to window open
    sent: float = float("nan")
    chunks: list = dataclasses.field(default_factory=list)  # arrival times
    status: int = 0
    finish: str | None = None
    error: str | None = None
    done: bool = False
    group: int | None = None

    @property
    def ok(self) -> bool:
        return self.done and self.error is None and self.status == 200 and bool(self.chunks)


def parse_prometheus(text: str) -> dict[str, float]:
    """``name`` or ``name{labels}`` -> value, as the exposition prints them."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


class Client:
    def __init__(self, port: int, model: str) -> None:
        self.base = f"http://127.0.0.1:{port}"
        self.model = model
        self.session: aiohttp.ClientSession | None = None
        self.t0 = 0.0  # perf_counter at window open
        # Set when what a plan sends past its scored part is not needed.
        self.enough = asyncio.Event()

    async def __aenter__(self) -> "Client":
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_connect=30),
        )
        return self

    async def __aexit__(self, *exc) -> None:
        await self.session.close()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def body(self, req: Request, stream: bool) -> bytes:
        d = {"model": self.model, "prompt": req.prompt, "max_tokens": req.max_tokens,
             "ignore_eos": True, "stream": stream, **req.sampling}
        if req.logprobs:
            d["logprobs"] = req.logprobs
        return json.dumps(d).encode()

    # ------------------------------------------------------------------

    async def ready(self) -> bool:
        try:
            async with self.session.get(self.base + "/health",
                                        timeout=aiohttp.ClientTimeout(total=5)) as r:
                return r.status == 200
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            return False

    async def metrics(self) -> dict[str, float]:
        async with self.session.get(self.base + "/metrics") as r:
            return parse_prometheus(await r.text())

    async def post_json(self, path: str, body: dict | None = None) -> dict:
        async with self.session.post(self.base + path, json=body or {}) as r:
            text = await r.text()
            if r.status != 200:
                raise RuntimeError(f"POST {path}: HTTP {r.status}: {text[:300]}")
            return json.loads(text)

    async def complete(self, req: Request) -> dict:
        """One request, not streamed: the whole answer with its logprobs."""
        async with self.session.post(
            self.base + "/v1/completions", data=self.body(req, False),
            headers={"Content-Type": "application/json"},
        ) as r:
            text = await r.text()
            if r.status != 200:
                raise RuntimeError(f"check request: HTTP {r.status}: {text[:300]}")
            return json.loads(text)

    async def stream(self, rec: Record, body: bytes) -> None:
        """Send one request now and record the arrival of each chunk."""
        rec.sent = self.now()
        last = b""
        try:
            async with self.session.post(
                self.base + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"},
            ) as r:
                rec.status = r.status
                if r.status != 200:
                    rec.error = (await r.text())[:200]
                    return
                async for line in r.content:
                    if not line.startswith(b"data: "):
                        continue
                    if line.startswith(b"data: [DONE]"):
                        rec.done = True
                        break
                    if b'"error"' in line:
                        rec.error = line[:200].decode(errors="replace")
                        continue
                    rec.chunks.append(self.now())
                    last = line
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:200]
            return
        if last:
            try:
                rec.finish = json.loads(last[6:])["choices"][0]["finish_reason"]
            except (ValueError, KeyError, IndexError):
                rec.error = "last chunk is not a completion chunk"

    # ------------------------------------------------------------------

    async def run_plan(self, plan: Plan, drain_limit_s: float) -> list[Record]:
        """Drive one plan on the clock whose zero is ``self.t0`` and return
        a record per request sent (open loop: per request of the plan).
        Whatever has not finished ``drain_limit_s`` after arrivals stop is
        cancelled and stays not ``done``."""
        if plan.kind == "open":
            return await self._run_open(plan, drain_limit_s)
        return await self._run_closed(plan, drain_limit_s)

    async def _run_open(self, plan: Plan, drain_limit_s: float) -> list[Record]:
        recs, tasks = [], []
        bodies = [self.body(q, True) for q in plan.requests]

        async def one(rec: Record, body: bytes) -> None:
            delay = max(0.0, rec.due - self.now())
            if rec.due >= plan.score_until:
                # Past the scored part: dropped as soon as it is not needed.
                try:
                    await asyncio.wait_for(self.enough.wait(), timeout=delay)
                    return
                except asyncio.TimeoutError:
                    pass
            else:
                await asyncio.sleep(delay)
            await self.stream(rec, body)

        for q, body in zip(plan.requests, bodies):
            rec = Record(q.idx, q.scored, len(q.prompt), q.max_tokens, q.due_s, group=q.group)
            recs.append(rec)
            tasks.append(asyncio.create_task(one(rec, body)))
        main = [t for t, r in zip(tasks, recs) if r.due < plan.score_until]
        if len(main) < len(tasks):
            # What goes on past the scored part is there for the profiler
            # alone: once that has enough and the scored part has drained,
            # the rest is dropped where it stands.
            await self._drain(main, plan.score_until + drain_limit_s, cancel=False)
            await self.enough.wait()
            deadline = self.now()
        else:
            deadline = plan.end_s + drain_limit_s
        await self._drain(tasks, deadline)
        return [r for r in recs if r.sent == r.sent or r.due < plan.score_until]

    async def _run_closed(self, plan: Plan, drain_limit_s: float) -> list[Record]:
        recs: list[Record] = []
        queue = iter(plan.requests)

        async def client() -> None:
            while self.now() < plan.end_s and not (
                    self.now() >= plan.score_until and self.enough.is_set()):
                q = next(queue, None)
                if q is None:
                    raise RuntimeError("the closed loop's pool of requests ran out")
                now = self.now()
                # A closed-loop request is due when its client is free.
                rec = Record(q.idx, 0.0 <= now < plan.score_until, len(q.prompt),
                             q.max_tokens, now, group=q.group)
                recs.append(rec)
                await self.stream(rec, self.body(q, True))

        delay = plan.start_s - self.now()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks = [asyncio.create_task(client()) for _ in range(plan.clients)]
        await self._drain(tasks, plan.end_s + drain_limit_s)
        return recs

    async def _drain(self, tasks: list, deadline: float, cancel: bool = True) -> None:
        if not tasks:
            return
        _, pending = await asyncio.wait(tasks, timeout=max(0.0, deadline - self.now()))
        if not cancel:
            return
        for t in pending:
            t.cancel()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        for res in results:
            if isinstance(res, Exception) and not isinstance(res, asyncio.CancelledError):
                raise res
