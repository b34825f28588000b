"""sad: sums of absolute differences against 9 windows of ref."""


def kernel(P, c, s):
    xp = P.xp
    blk, ref = c["blk"], s["ref"]
    outs = []
    for dy in range(0, 9, 4):
        for dx in range(0, 9, 4):
            win = ref[dy:dy + 16, dx:dx + 16]
            outs.append(xp.sum(xp.abs(blk - win), axis=(1, 2)))
    return xp.stack(outs, axis=1)
