"""Device idle time inside the program's ``kvcomm.wire.encode`` spans (the
int8 quantize and its device-to-host read), per ``kvcomm.share`` span: the
encode half of what ``share_stall_ms`` reads (``programspans``).  One
share encodes K and V, so it holds two encode spans."""
import programspans


def read(ctx):
    return programspans.stall_ms(ctx.trace, ("kvcomm.wire.encode",),
                                 per=programspans.SHARE)
