"""Device idle time inside the program's ``kvcomm.wire.decode`` spans (the
host-to-device upload and the dequantize), per ``kvcomm.share`` span: the
decode half of what ``share_stall_ms`` reads (``programspans``)."""
import programspans


def read(ctx):
    return programspans.stall_ms(ctx.trace, ("kvcomm.wire.decode",),
                                 per=programspans.SHARE)
