"""Open-loop load: every request is sent when it is due, whether or not
earlier ones have finished, as a streamed ``POST /v1/completions`` over
loopback HTTP, and timed from the moment it was due."""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import List, Optional, Tuple

from chipbench.traffic import Req


@dataclasses.dataclass
class Result:
    req: Req
    due: float                     # absolute perf_counter time
    sent: Optional[float] = None
    rid: Optional[int] = None      # the gateway's request id
    chunks: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    finish: Optional[str] = None
    pool: Optional[str] = None
    compressed: Optional[bool] = None
    error: Optional[str] = None

    @property
    def first(self) -> Optional[float]:
        return self.chunks[0][0] if self.chunks else None


async def send(host: str, port: int, res: Result) -> None:
    """Wait until the request is due, send it, and read its stream."""
    delay = res.due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    res.sent = time.perf_counter()
    r = res.req
    body = json.dumps({"prompt": r.prompt, "max_tokens": r.l_out,
                       "stream": True, "category": r.category}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(None, 2)[1])
        if status != 200:
            rest = await reader.read()
            res.error = f"HTTP {status}: {rest[:200]!r}"
            return
        while True:
            ev = await reader.readuntil(b"\n\n")
            now = time.perf_counter()
            if ev.startswith(b"data: [DONE]"):
                res.done = now
                return
            if not ev.startswith(b"data: "):
                continue
            obj = json.loads(ev[6:])
            ch = obj["choices"][0]
            if res.rid is None:
                res.rid = int(obj["id"].rsplit("-", 1)[1])
            ids = ch["token_ids"]
            if ids:
                res.chunks.append((now, len(ids)))
                res.tokens.extend(ids)
            if ch["finish_reason"] is not None:
                res.finish = ch["finish_reason"]
                ann = obj.get("fleetopt") or {}
                res.pool = ann.get("pool")
                res.compressed = ann.get("compressed")
    except (ConnectionError, asyncio.IncompleteReadError,
            asyncio.LimitOverrunError, ValueError) as e:
        res.error = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()
